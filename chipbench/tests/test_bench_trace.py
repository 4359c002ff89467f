"""The trace reduction on synthetic traces and on one recorded on the CPU."""
import pytest

from chipbench import trace


def ms(x):
    return x * 1_000_000.0


def synthetic():
    tr = trace.Trace()
    # device: busy 0-10 ms, a nested op inside a while, idle 10-15 ms,
    # busy 15-18 ms, idle 18-20 ms
    tr.ops["/device:TPU:0"] = [
        ("%while.1 = (s32[]) while(...)", ms(0), ms(10)),
        ("%fusion.2 = bf16[8] fusion(...)", ms(1), ms(4)),
        ("%fusion.3 = bf16[8] fusion(...)", ms(4), ms(9)),
        ("%copy.4 = bf16[8] copy(...)", ms(15), ms(18)),
    ]
    tr.host = [("window", ms(0), ms(20)),
               ("serve_once", ms(0), ms(20)),
               ("prefill", ms(0), ms(2)),
               ("poll", ms(10), ms(14)),
               ("decode", ms(15), ms(19))]
    return tr


def test_union_gaps_overlap():
    merged = trace.union([(5, 7), (0, 2), (1, 3), (7, 8)])
    assert merged == [(0, 3), (5, 8)]
    assert trace.overlap(merged, 2, 6) == 2
    assert trace.gaps(merged, 0, 10) == [(3, 5), (8, 10)]


def test_reduce_busy_idle_top_ops_and_gap_attribution():
    out = trace.reduce(synthetic())
    assert out["window_s"] == pytest.approx(0.020)
    assert out["busy_s"] == pytest.approx(0.013)
    assert out["idle_share"] == pytest.approx(0.35)
    # the while op holds fusion.2 and fusion.3: only the leaves count
    ops = dict(out["device_ops"])
    assert set(ops) == {"fusion.2", "fusion.3", "copy.4"}
    assert ops["fusion.3"] == pytest.approx(0.005)
    # 10-15 ms: poll covers 4 of 5 ms; 18-20 ms: decode covers 1 ms, and
    # the coarse serve_once names nothing while a finer span overlaps
    gaps = dict(out["idle_gaps"])
    assert gaps == pytest.approx({"poll": 0.005, "decode": 0.002})


def test_idle_within_spans():
    tr = synthetic()
    tr.host.append(("serve_once", ms(30), ms(31)))       # outside the window
    # inside serve_once spans (0-20 ms), 7 ms idle
    assert trace.idle_within(tr, "serve_once", ms(0), ms(20)) == pytest.approx(0.35)
    assert trace.idle_within(tr, "serve_once", ms(0), ms(20),
                             containing="nothing") is None


def test_reduce_averages_chips():
    tr = synthetic()
    tr.ops["/device:TPU:1"] = [("%fusion.9 = f32[] fusion()", ms(0), ms(20))]
    out = trace.reduce(tr)
    assert out["busy_s"] == pytest.approx((0.013 + 0.020) / 2)


def test_load_a_recorded_cpu_trace(tmp_path):
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("window"):
        for _ in range(2):
            with TraceAnnotation("step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    tr = trace.load(str(tmp_path), {"window", "step"}, device_plane="/host:CPU",
                    op_line="tf_XLAPjRtCpuClient")
    assert len(trace.spans(tr, "step")) == 2
    lo, hi = trace.window(tr)
    assert all(lo <= s and e <= hi for s, e in trace.spans(tr, "step"))
    out = trace.reduce(tr)
    assert 0 < out["busy_s"] <= out["window_s"]
    with pytest.raises(ValueError, match="XLA Ops"):
        trace.load(str(tmp_path), {"window"})
