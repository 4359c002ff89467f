"""Exact percentiles, the whole-step window rate and the metric readers."""
import types

import numpy as np
import pytest

from chipbench import cells, stats


def test_percentile_is_exact_over_all_samples():
    v = list(range(1, 101))                       # 1..100
    assert stats.percentile(v, 50) == pytest.approx(50.5)
    assert stats.percentile(v, 95) == pytest.approx(95.05)
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None
    # no binning: moving one sample moves the tail by exactly its share
    w = v[:-1] + [1000]
    assert stats.percentile(w, 99) == pytest.approx(99 + 0.01 * 901)


def test_window_rate_takes_all_work_of_whole_steps():
    # 9 steps of 16,384 tokens began inside a 10 s budget and the last
    # ended at 12.5 s: all nine count, over 12.5 s
    assert stats.window_rate(9 * 16384, 100.0, 112.5) == pytest.approx(9 * 16384 / 12.5)


def fake_run(kind="serve", judged_by="latency"):
    cell = types.SimpleNamespace(traffic={"kind": kind, "judged_by": judged_by,
                                          "drain_s": 10.0})
    return types.SimpleNamespace(
        cell=cell, w0=0.0, w1=20.0, chips=1, peak={"bf16_flops_per_s": 100.0},
        window_flops=500.0, busy_flops_s=10.0,
        work={"tokens": 1000, "steps": 4, "batch_s": [1.0, 3.0, 2.0]},
        spans=[("next_batch", 1.0, 1.5), ("next_batch", 2.0, 2.5), ("step", 1.5, 1.6)],
        requests={"a": {"due": 1.0, "polled": 1.5, "arrived": 2.0},
                  "b": {"due": 2.0, "polled": 2.1, "arrived": None}},
        summary={"idle_share": 0.25}, traced=None,
        spans_named=lambda n: [(a, b) for m, a, b in
                               [("next_batch", 1.0, 1.5), ("next_batch", 2.0, 2.5)] if m == n])


def read(name, run):
    return cells.metric_reader(name)(run)


def test_latency_counts_an_unlanded_request_at_the_drain_limit():
    run = fake_run()
    # a: 1.0 s; b never landed: 20 + 10 - 2 = 28 s
    assert read("request_latency_p50_s", run) == pytest.approx(np.percentile([1.0, 28.0], 50))
    assert read("queue_wait_p95_s.latency", run) == pytest.approx(np.percentile([0.5, 0.1], 95))


def test_readers_of_rates_shares_and_spans():
    run = fake_run()
    assert read("serve_tokens_per_s", run) == pytest.approx(50.0)
    assert read("train_tokens_per_s", run) is None
    assert read("serve_batch_s.latency", run) == pytest.approx(2.0)
    assert read("mfu.throughput", run) == pytest.approx(100 * 500 / (20 * 100))
    assert read("mfu.latency", run) == pytest.approx(100 * 500 / (10 * 100))
    assert read("device_idle_share.throughput", run) == pytest.approx(25.0)
    assert read("loader_s_per_step.train", run) == pytest.approx(1.0 / 4)
    run.peak = {}
    assert read("mfu.train", run) is None


def test_every_metric_of_the_benchmark_has_a_reader():
    import json
    from chipbench.tests.small_cells import SERVE_BENCH
    bench = json.loads((cells.ROOT / "BENCHMARK.json").read_text())
    for b in (bench, SERVE_BENCH):
        for m in b["end_to_end"] + b["per_layer"]:
            assert callable(cells.metric_reader(m["name"]))
        for w in b["workloads"]:
            cell = cells.load(w["name"], b if b is SERVE_BENCH else None)
            assert cell.limits["numbers"]
            assert cell.traffic["kind"] in ("train", "serve")
