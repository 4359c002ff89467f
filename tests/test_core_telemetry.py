"""Telemetry layer (ISSUE 9): mergeable log-bucketed latency histograms,
the process-local metrics registry, trace sampling through provenance,
the flight recorder, and the HTTP scrape endpoint.

The merge tests are the load-bearing ones: fabric-wide aggregation is
only correct because merging per-worker histograms bucket-wise is *exact*
(fixed power-of-two boundaries), so percentiles over the merged state
equal percentiles over a single histogram fed every sample.
"""
import json
import random
import threading
import urllib.error
import urllib.request

import pytest

from repro.core.telemetry import (FlightRecorder, LatencyHistogram,
                                  MetricsRegistry, bucket_index,
                                  merge_histogram_states, metric_key,
                                  serve_scrape, split_metric_key,
                                  summarize_histogram_state)


# -- LatencyHistogram ---------------------------------------------------------

def test_bucket_index_boundaries():
    assert bucket_index(0.0) == 0
    assert bucket_index(-1.0) == 0
    assert bucket_index(1e-6) == 1          # 1µs -> bucket 1
    assert bucket_index(1.5e-6) == 1
    assert bucket_index(2e-6) == 2
    assert bucket_index(1.0) == 20          # 1s ≈ 2^20 µs
    assert bucket_index(1e9) < 64           # clamped: no IndexError ever


def test_percentile_midpoint_and_count():
    h = LatencyHistogram()
    h.record(0.001, n=5)                    # 1ms x5
    h.record(0.1)                           # 100ms x1
    assert h.count == 6
    assert h.sum_seconds == pytest.approx(0.105)
    # p50 lands in the 1ms bucket, p99 in the 100ms bucket; answers are
    # geometric bucket midpoints, so within the power-of-two width
    assert 0.0007 < h.percentile(0.5) < 0.0015
    assert 0.06 < h.percentile(0.99) < 0.13
    s = h.summary()
    assert s["count"] == 6
    assert s["p50_ms"] < s["p99_ms"]


def test_percentile_empty_and_bad_q():
    h = LatencyHistogram()
    assert h.percentile(0.5) == 0.0
    with pytest.raises(ValueError):
        h.percentile(1.5)


def test_merge_is_exact():
    """Percentiles over merged histograms == percentiles over one
    histogram fed all samples — the fabric-aggregation invariant."""
    rng = random.Random(7)
    samples = [rng.uniform(1e-6, 0.5) for _ in range(4_000)]
    whole = LatencyHistogram()
    parts = [LatencyHistogram() for _ in range(4)]
    for i, s in enumerate(samples):
        whole.record(s)
        parts[i % 4].record(s)
    merged = LatencyHistogram()
    for p in parts:
        merged.merge(p)
    assert merged.count == whole.count
    assert merged.sum_seconds == pytest.approx(whole.sum_seconds)
    for q in (0.1, 0.5, 0.9, 0.99):
        assert merged.percentile(q) == whole.percentile(q)


def test_serialization_round_trip_and_state_merge():
    h = LatencyHistogram()
    h.record(0.004, n=3)
    h.record(2.0)
    state = h.to_dict()
    assert json.loads(json.dumps(state)) == json.loads(json.dumps(state))
    back = LatencyHistogram.from_dict(json.loads(json.dumps(state)))
    assert back.count == h.count
    assert back.summary() == h.summary()
    # merge_histogram_states == instance merge, on the wire format
    into = {"k": h.to_dict()}
    merge_histogram_states(into, {"k": h.to_dict(), "k2": h.to_dict()})
    assert into["k"]["n"] == 2 * h.count
    assert into["k2"]["n"] == h.count
    summ = summarize_histogram_state(into)
    assert summ["k"]["count"] == 2 * h.count


def test_state_merge_does_not_alias_source():
    """First insert must deep-copy: merging more state into the target
    must never mutate the original report (the fabric merges the same
    per-worker dicts every ``status()`` call)."""
    src = {"k": {"b": {"3": 2}, "n": 2, "s": 1.0}}
    into: dict = {}
    merge_histogram_states(into, src)
    merge_histogram_states(into, src)
    assert src["k"]["n"] == 2                # untouched
    assert into["k"]["n"] == 4


def test_timer_uses_injected_clock():
    fake = [10.0]
    h = LatencyHistogram(clock=lambda: fake[0])
    with h.timer(n=4):
        fake[0] += 0.25
    assert h.count == 4
    assert h.sum_seconds == pytest.approx(1.0)      # 0.25s x4


def test_record_many_matches_individual_records():
    a, b = LatencyHistogram(), LatencyHistogram()
    durations = [0.001, 0.002, 0.5, 0.0001]
    a.record_many(durations)
    for d in durations:
        b.record(d)
    assert a.to_dict() == b.to_dict()


def test_concurrent_record_and_collect():
    """Writer threads hammer record() while a reader collects summaries:
    no tearing, and the final count is exact (no lost increments)."""
    h = LatencyHistogram()
    n_threads, per_thread = 8, 2_000
    stop = threading.Event()

    def reader():
        while not stop.is_set():
            s = h.summary()
            assert s["count"] >= 0

    r = threading.Thread(target=reader)
    ws = [threading.Thread(
        target=lambda: [h.record(0.001) for _ in range(per_thread)])
        for _ in range(n_threads)]
    r.start()
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    stop.set()
    r.join()
    assert h.count == n_threads * per_thread


# -- metric keys --------------------------------------------------------------

def test_metric_key_round_trip_and_sorting():
    k = metric_key("rpc_seconds", {"op": "read", "addr": "x"})
    assert k == 'rpc_seconds{addr="x",op="read"}'      # labels sorted
    name, labels = split_metric_key(k)
    assert name == "rpc_seconds"
    assert labels == 'addr="x",op="read"'
    assert split_metric_key("plain") == ("plain", "")


# -- MetricsRegistry ----------------------------------------------------------

def test_registry_get_or_create_and_merged():
    reg = MetricsRegistry()
    h1 = reg.histogram("process_seconds", processor="parse")
    h2 = reg.histogram("process_seconds", processor="parse")
    assert h1 is h2
    reg.histogram("process_seconds", processor="route").record(0.1, n=2)
    h1.record(0.001, n=3)
    assert reg.merged("process_seconds").count == 5
    summ = reg.summaries()
    assert summ['process_seconds{processor="parse"}']["count"] == 3


def test_registry_sources_collect_and_render():
    reg = MetricsRegistry()
    reg.register_source(
        "connector", lambda: {"rss": {"records": 7, "state": "RUNNING",
                                      "lag": None}})
    reg.histogram("poll_seconds", connector="rss").record(0.002)
    out = reg.collect()
    assert out["gauges"]["connector"]["rss"]["records"] == 7
    text = reg.render_text()
    # numeric gauges render; strings/None are skipped; histograms render
    # as summary-style quantile/count/sum lines
    assert 'repro_connector_records{connector="rss"} 7' in text
    assert "state" not in text
    assert 'repro_poll_seconds{connector="rss",quantile="0.5"}' in text
    assert 'repro_poll_seconds_count{connector="rss"} 1' in text
    json.loads(reg.to_json())               # valid JSON dump


def test_registry_source_errors_are_isolated():
    reg = MetricsRegistry()
    reg.register_source("bad", lambda: 1 / 0)
    reg.register_source("good", lambda: {"x": {"v": 1}})
    out = reg.collect()
    assert out["gauges"]["good"]["x"]["v"] == 1
    assert out["gauges"]["bad"] == {}       # isolated, not fatal


# -- FlightRecorder -----------------------------------------------------------

def test_flight_recorder_ring_and_dump(tmp_path):
    fake = [100.0]
    fr = FlightRecorder(capacity=4, clock=lambda: fake[0])
    for i in range(10):
        fake[0] += 1.0
        fr.record({"i": i})
    snaps = fr.snapshots()
    assert len(snaps) == 4                       # ring kept the last N
    assert [s["status"]["i"] for s in snaps] == [6, 7, 8, 9]
    assert snaps[0]["ts"] == pytest.approx(107.0)
    path = tmp_path / "flight.json"
    fr.dump(path)
    assert [e["status"]["i"] for e in json.loads(path.read_text())] \
        == [6, 7, 8, 9]


# -- ScrapeServer -------------------------------------------------------------

def test_scrape_server_serves_metrics_text():
    srv = serve_scrape(lambda: "repro_up 1\n")
    try:
        body = urllib.request.urlopen(srv.url, timeout=5).read().decode()
        assert body == "repro_up 1\n"
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/nope", timeout=5)
    finally:
        srv.close()
        srv.close()                              # idempotent


# -- Tracer: spans and counters -----------------------------------------------

class _FakeClock:
    def __init__(self, step=0.5):
        self.t, self.step = 0.0, step

    def __call__(self):
        self.t += self.step
        return self.t


def test_tracer_parents_and_trace_ids():
    from repro.core.telemetry import Tracer
    tr = Tracer(clock=_FakeClock())
    with tr.span("train/step", trace_id=7):
        with tr.span("loader/next_batch"):
            with tr.span("loader/poll"):
                pass
        tr.record("compile", 0.1, 0.2)
    with tr.span("other"):
        pass
    recs = {r.name: r for r in tr.spans()}
    step = recs["train/step"]
    assert step.parent_id is None and step.trace_id == 7
    assert recs["loader/next_batch"].parent_id == step.id
    assert recs["loader/poll"].parent_id == recs["loader/next_batch"].id
    assert recs["loader/poll"].trace_id == 7          # inherited
    assert recs["compile"].parent_id == step.id
    assert (recs["compile"].t0, recs["compile"].t1) == (0.1, 0.2)
    assert recs["other"].parent_id is None and recs["other"].trace_id is None
    # children end before their parents; a parent holds its children
    assert [r.name for r in tr.spans()][:2] == ["loader/poll",
                                                "loader/next_batch"]
    assert step.t0 < recs["loader/poll"].t0 < recs["loader/poll"].t1 < step.t1
    assert [r.name for r in tr.spans("loader/poll")] == ["loader/poll"]


def test_tracer_parents_are_per_thread():
    from repro.core.telemetry import Tracer
    tr = Tracer()
    with tr.span("main"):
        t = threading.Thread(target=lambda: tr.span("worker").__enter__()
                             .__exit__(None, None, None))
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert {r.name: r.parent_id for r in tr.spans()}["worker"] is None


def test_tracer_ring_bound_and_dropped():
    from repro.core.telemetry import Tracer
    clock = _FakeClock(step=1.0)
    tr = Tracer(capacity=3, clock=clock)
    for i in range(5):
        with tr.span("s", trace_id=i):
            pass
    kept = tr.spans()
    assert [r.trace_id for r in kept] == [2, 3, 4]
    assert tr.dropped == 2
    # the latest end among the dropped: span 1 ran from t=3 to t=4
    assert tr.dropped_until == 4.0
    # the histogram kept every span, dropped or not
    assert tr.registry.histogram("span_seconds", span="s").count == 5
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_tracer_histogram_sums_are_exact():
    from repro.core.telemetry import Tracer
    tr = Tracer(clock=_FakeClock(step=0.25))
    for _ in range(4):
        with tr.span("a"):
            pass
    tr.record("b", 1.0, 3.5)
    h = tr.registry.histogram("span_seconds", span="a")
    assert h.count == 4 and h.sum_seconds == 1.0        # 4 x 0.25 s
    assert tr.registry.histogram("span_seconds", span="b").sum_seconds == 2.5
    assert sum(r.t1 - r.t0 for r in tr.spans("a")) == h.sum_seconds


def test_tracer_counters_render_beside_histograms():
    from repro.core.telemetry import Tracer
    reg = MetricsRegistry()
    reg.histogram("poll_seconds", connector="rss").record(0.002)
    tr = Tracer(registry=reg)
    tr.count("loader_records", 64)
    tr.count("loader_records", 3)
    tr.count("loader_batches")
    assert tr.value("loader_records") == 67 and tr.value("never") == 0
    with tr.span("loader/poll"):
        pass
    snap = reg.collect()
    assert snap["counters"] == {"loader_records": 67, "loader_batches": 1}
    assert 'span_seconds{span="loader/poll"}' in snap["histograms"]
    text = reg.render_text()
    assert "repro_loader_records_total 67" in text
    assert "repro_loader_batches_total 1" in text
    assert 'repro_span_seconds_count{span="loader/poll"} 1' in text
    assert 'repro_poll_seconds_count{connector="rss"} 1' in text
    json.loads(reg.to_json())


def test_tracer_labelled_counters_are_separate():
    from repro.core.telemetry import Tracer
    tr = Tracer()
    tr.count("loader_starved_polls", 2, loader="0")
    tr.count("loader_starved_polls", loader="1")
    assert tr.value("loader_starved_polls", loader="0") == 2
    assert tr.value("loader_starved_polls", loader="1") == 1
    assert tr.value("loader_starved_polls") == 0
    text = tr.registry.render_text()
    assert 'repro_loader_starved_polls_total{loader="0"} 2' in text
    assert 'repro_loader_starved_polls_total{loader="1"} 1' in text


def test_tracer_annotation_factory_wraps_each_span():
    from repro.core.telemetry import Tracer
    events = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name
            events.append(("make", name))

        def __enter__(self):
            events.append(("enter", self.name))

        def __exit__(self, *exc):
            events.append(("exit", self.name))

    tr = Tracer()
    tr.set_annotation(FakeAnnotation)
    with tr.span("train/step"):
        with tr.span("train/dispatch"):
            pass
    tr.record("compile", 0.0, 1.0)          # back-dated: no annotation
    assert events == [("make", "train/step"), ("enter", "train/step"),
                      ("make", "train/dispatch"), ("enter", "train/dispatch"),
                      ("exit", "train/dispatch"), ("exit", "train/step")]
    # a factory may decline (no profiler session): the span still records
    tr.set_annotation(lambda name: None)
    with tr.span("quiet"):
        pass
    tr.set_annotation(None)
    assert [r.name for r in tr.spans()][-1] == "quiet"


def test_tracer_exception_closes_span():
    from repro.core.telemetry import Tracer
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("outer"):
            raise RuntimeError("boom")
    with tr.span("after"):
        pass
    assert {r.name: r.parent_id for r in tr.spans()}["after"] is None


def test_process_tracer_functions():
    from repro.core import telemetry
    before = telemetry.tracer().value("test_process_counter")
    telemetry.count("test_process_counter", 2)
    with telemetry.span("test/process_span", trace_id=3):
        pass
    tr = telemetry.tracer()
    assert tr.value("test_process_counter") == before + 2
    assert tr.spans("test/process_span")[-1].trace_id == 3
