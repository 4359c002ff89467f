"""The generator's schedule and data repeat from the seed; every seed gets
the same amount of work."""
import itertools

import numpy as np

from chipbench import workload
from chipbench.tests.small_cells import load

BIG = 2**31 + 12345


def traffic(name):
    return load(name).traffic


def test_documents_repeat_from_the_seed():
    t = traffic("mamba2-370m.train_stream")
    a = list(itertools.islice(workload.doc_schedule(t, BIG), 20))
    b = list(itertools.islice(workload.doc_schedule(t, BIG), 20))
    c = list(itertools.islice(workload.doc_schedule(t, 7), 20))
    assert a == b and a != c
    lo, hi = t["doc_words"]
    for _, _, text in a:
        assert lo <= len(text.split()) <= hi and text.isascii()
    # open loop: due times follow the tokens sent at the fixed rate
    dues = [d for _, d, _ in a]
    toks = np.cumsum([0] + [workload.doc_tokens(x) for _, _, x in a[:-1]])
    assert np.allclose(dues, toks / t["tokens_per_s"])


def test_requests_repeat_from_the_seed_with_the_same_work():
    t = traffic("hymba-1.5b.serve_long_prompt")
    a = workload.requests(t, BIG, 30.0)
    assert a == workload.requests(t, BIG, 30.0)
    b = workload.requests(t, 8, 30.0)
    assert [r["due"] for r in a] != [r["due"] for r in b]
    # the same count in each segment, and the same prompt sizes in another
    # order, inside the traffic's clip
    spec = t["prompt_bytes"]["lognormal"]
    for reqs in (a, b):
        due = np.array([r["due"] for r in reqs])
        lead = t["lead_s"]
        assert ((due >= lead) & (due < lead + 30.0)).sum() == round(t["rate_per_s"] * 30)
        sizes = [len(r["prompt"].encode()) for r in reqs]
        assert spec["min"] <= min(sizes) and max(sizes) <= spec["max"]
    assert (sorted(len(r["prompt"]) for r in a) == sorted(len(r["prompt"]) for r in b)
            != [len(r["prompt"]) for r in a])
    assert np.all(np.diff([r["due"] for r in a]) >= 0)


def test_lognormal_sizes_are_one_draw_in_the_seed_order():
    spec = {"lognormal": {"median": 512, "sigma": 0.6, "min": 64, "max": 1023}}
    a = workload.prompt_sizes(spec, 50, 1)
    b = workload.prompt_sizes(spec, 50, 2)
    assert sorted(a) == sorted(b) and a != b
    assert min(a) >= 64 and max(a) <= 1023
