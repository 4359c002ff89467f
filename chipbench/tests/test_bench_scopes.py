"""The program's own spans and named scopes, read by the per-layer metrics
of ``scopes.py`` in one traced run of the train cell on the CPU at a small
size.

On the CPU the step's ops run on XLA's Eigen worker threads, in parallel,
among runtime events (``ThunkExecutor::Execute``, ``...::...``) and an
``end: <op>`` marker inside each op. The run here takes each worker thread
as a device line of its own and keeps its op events alone, as a TPU's
``XLA Ops`` line has them, so that the reduction sees the program's own
ops and times.
"""
import glob
import os
import time

import pytest

from chipbench import harness, scopes, trace
from chipbench.tests.small_cells import SMALL, load

SEED = 2**31 + 17
NEW = ("poll_s_per_step.train", "pack_s_per_step.train",
       "ssd_device_s_per_step.train", "mixer_proj_device_s_per_step.train",
       "head_device_s_per_step.train", "optimizer_device_s_per_step.train",
       "layer_stack_device_s_per_step.train")
PROGRAM_SPANS = ("train", "train/step", "train/dispatch", "loader/next_batch",
                 "loader/poll", "loader/pack")


def _cpu_ops(directory):
    """``{thread: [(op, start_ns, end_ns)]}`` of XLA's CPU worker threads."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    pd = ProfileData.from_file(max(files, key=os.path.getmtime))
    ops = {}
    for plane in pd.planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            if line.name.startswith("tf_XLAEigen"):
                ev = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events
                      if not e.name.startswith("end: ") and "::" not in e.name]
                if ev:
                    ops[line.name] = ev
    return ops


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    from repro import configs
    seen = {}
    load_trace, result = harness.load_trace, harness.result

    def load_ops(run, directory, names):
        load_trace(run, directory, names)
        run.traced.ops = _cpu_ops(directory)
        run.summary = trace.reduce(run.traced)
        kw = {"device_plane": "/host:CPU", "op_line": "tf_XLAPjRtCpuClient"}
        seen["xplane"] = trace.load(directory, set(names) | set(PROGRAM_SPANS)
                                    | {trace.WINDOW}, **kw)

    def keep_run(run):
        seen["run"] = run
        return result(run)

    cell = load("mamba2-370m.train_stream")
    cell.traffic.update(SMALL["train"])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "load_trace", load_ops)
        mp.setattr(harness, "result", keep_run)
        out = harness.run_cell(
            cell, SEED, 2.0, True, time.monotonic(),
            cfg=configs.get_reduced("mamba2-370m"), require_tpu=False,
            run_dir=tmp_path_factory.mktemp("scopes") / "run",
            compile_cache=False)
    return out, seen["run"], seen["xplane"]


def test_the_seven_readers_read_numbers(traced):
    out, run, _ = traced
    assert out["correct"], out["checks"]
    got = {k: v["value"] for k, v in out["metrics"].items()}
    for name in NEW:
        assert isinstance(got.get(name), float), name
        assert got[name] >= 0.0, name
    for name in NEW[2:]:
        assert got[name] > 0.0, name
    assert run.work["steps"] > 0


def test_scopes_sum_to_the_window_leaf_op_seconds(traced):
    _, run, _ = traced
    table = scopes.per_scope(run)
    assert table is not None and "unscoped" in table
    assert {"embed", "conv", "gate_norm", "ssd", "mixer_proj", "head",
            "optimizer", "layer_stack"} <= set(table)
    # the total that trace.reduce ranks device_ops by
    lo, hi = trace.window(run.traced)
    total = sum(max(0.0, min(e, hi) - max(s, lo)) * 1e-9 / len(run.traced.ops)
                for ops in run.traced.ops.values()
                for _, s, e in trace.leaves(ops)) / run.work["steps"]
    assert sum(table.values()) == pytest.approx(total, rel=0.01)
    # most of it under a named scope (the CPU backend turns some reductions
    # into reduce-window fusions that carry no op_name), the SSD the most
    assert sum(v for k, v in table.items() if k != "unscoped") > 0.75 * total
    assert max(table, key=table.get) == "ssd"


def test_poll_and_pack_lie_inside_next_batch_time(traced):
    out, _, _ = traced
    got = {k: v["value"] for k, v in out["metrics"].items()}
    inner = got["poll_s_per_step.train"] + got["pack_s_per_step.train"]
    assert 0.0 < inner <= got["loader_s_per_step.train"]


def _inside(spans, outer):
    return all(any(a <= s and e <= b for a, b in outer) for s, e in spans)


def test_program_spans_share_the_profiler_clock(traced):
    _, run, xp = traced
    steps = trace.spans(xp, "train/step")
    assert len(steps) >= run.work["steps"]
    assert len(trace.spans(xp, "train")) == len(steps)   # xprof step markers
    dispatch = trace.spans(xp, "train/dispatch")
    assert dispatch and _inside(dispatch, trace.spans(xp, "step"))
    next_batch = trace.spans(xp, "next_batch")
    for name in ("loader/next_batch", "loader/pack"):
        assert trace.spans(xp, name), name
    # a poll at this size brings rows for several steps: the window may
    # hold none
    for name in ("loader/next_batch", "loader/poll", "loader/pack"):
        assert _inside(trace.spans(xp, name), next_batch), name
    assert _inside(dispatch + trace.spans(xp, "loader/next_batch"), steps)


def test_readers_return_nothing_without_the_program(traced, monkeypatch):
    """A program without spans or an op table (one that predates them)
    reads None, and nothing raises."""
    import sys
    _, run, _ = traced
    from chipbench import cells
    monkeypatch.setitem(sys.modules, "repro.core.telemetry", None)
    monkeypatch.setitem(sys.modules, "repro.runtime", None)
    monkeypatch.delattr(run, "_scope_s_per_step")
    for name in NEW:
        assert cells.metric_reader(name)(run) is None, name


def test_the_table_reports_ops_whose_members_span_scopes(traced, capsys,
                                                          monkeypatch):
    """Each op's device time is credited whole to one scope; the printed
    table says how much of it lies in ops fused from several scopes."""
    _, run, _ = traced
    from repro.runtime.tracing import op_mixed_scopes
    mixed = op_mixed_scopes(scopes.PROGRAM)
    assert mixed
    monkeypatch.delattr(run, "_scope_s_per_step")
    assert scopes.per_scope(run) is not None
    err = capsys.readouterr().err
    assert "ops whose fused members come from more than one scope: " in err
    assert "    credited to " in err
