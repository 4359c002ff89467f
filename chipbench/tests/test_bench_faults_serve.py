"""The serving cells' comparison: a sound run is correct; a token altered
where it is produced is not."""
import pytest


@pytest.mark.parametrize("cell", ["hymba-1.5b.serve_long_prompt",
                                  "hymba-1.5b.serve_long_output"])
def test_sound_run_is_correct(run_small, cell):
    out = run_small(cell, 2**31 + 3)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0


def test_altered_token_is_caught(run_small):
    def fault(server):
        decode = server._decode

        def altered(params, cache, tok):
            logits, cache = decode(params, cache, tok)
            return logits.at[:, 7].add(1e4), cache

        server._decode = altered

    out = run_small("hymba-1.5b.serve_long_prompt", 9, fault)
    assert not out["correct"]
    gap = out["checks"]["served_logit_gap"]
    assert gap["value"] > gap["limit"]
