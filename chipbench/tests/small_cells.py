"""The cells' traffic at a size the CPU runs in seconds, and the serving
cells that the tests drive.

The serving cells are not in ``BENCHMARK.json``: the server decodes short
prompts from a padding position (PERF.md, Open questions), so on the
traffic's lognormal prompt lengths no run of the program is correct. The
tests drive the serving harness with prompts that fill the window, where
the server is sound, to show that the comparison passes a sound run and
fails a planted fault. Their limit is for the reduced configuration the
tests run, not a limit of the chip's cells.
"""

SMALL = {
    "train": dict(batch=2, seq=64, tokens_per_s=4000),
    "long_prompt": dict(batch=2, prompt_len=32, prompt_bytes=31, max_new=4,
                        rate_per_s=4.0, check_requests=2, drain_s=20.0),
    "long_output": dict(batch=4, prompt_len=16, prompt_bytes=15, max_new=8,
                        rate_per_s=20.0),
}

_LP, _LO = "hymba-1.5b.serve_long_prompt", "hymba-1.5b.serve_long_output"


def _metric(name, unit, better, cell):
    return {"name": name, "unit": unit, "better": better, "workloads": [cell]}


SERVE_BENCH = {
    "configs": [{"name": "hymba-1.5b", "file": "chipbench/configs/hymba-1.5b.json"}],
    "workloads": [{"name": _LP, "config": "hymba-1.5b", "traffic": "long_prompt", "chips": 1},
                  {"name": _LO, "config": "hymba-1.5b", "traffic": "long_output", "chips": 1}],
    "end_to_end": [_metric("serve_tokens_per_s", "tokens/s", "higher", _LO),
                   _metric("request_latency_p50_s", "s", "lower", _LP),
                   {"name": "setup_s", "unit": "s", "better": "lower"}],
    "per_layer": [_metric("queue_wait_p95_s.latency", "s", "lower", _LP),
                  _metric("serve_batch_s.latency", "s", "lower", _LP),
                  _metric("mfu.throughput", "%", "higher", _LO),
                  _metric("mfu.latency", "%", "higher", _LP),
                  _metric("device_idle_share.throughput", "%", "lower", _LO),
                  _metric("batch_idle_share.latency", "%", "lower", _LP)],
    "limits": {c: {"numbers": {"served_logit_gap": {"limit": 0.5}}} for c in (_LP, _LO)},
}


def load(name):
    """A cell of ``BENCHMARK.json`` or one of the serving cells above."""
    from chipbench import cells
    names = {w["name"] for w in SERVE_BENCH["workloads"]}
    return cells.load(name, SERVE_BENCH if name in names else None)
