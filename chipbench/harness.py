"""One run of one cell: the broker and the generator as processes of their
own, the device side in this process through the program's entry points,
then the metrics, the correctness checks and the result line.

The drivers (``train.py``, ``serve.py``, chosen by the traffic's ``kind``)
fill a ``Run``; metric readers (``metrics/<name>.py``) read it.
"""
from __future__ import annotations

import gc
import importlib
import multiprocessing as mp
import shutil
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

from . import cells

RUN_DIR = cells.HERE / ".run"
STOP_TIMEOUT_S = 30.0
START_TIMEOUT_S = 120.0


@dataclass
class Check:
    """A number compared with its limit: ``ok`` when ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


@dataclass
class Run:
    cell: cells.Cell
    seed: int
    seconds: float
    trace: bool
    t_start: float
    cfg: object = None
    chips: int = 1
    peak: dict = field(default_factory=dict)
    w0: float = 0.0                     # window, host monotonic clock
    w1: float = 0.0
    setup_s: float = 0.0
    spans: list = field(default_factory=list)     # (name, t0, t1)
    work: dict = field(default_factory=dict)      # driver's counts
    requests: dict = field(default_factory=dict)  # id -> due/polled/arrived
    window_flops: float = 0.0
    busy_flops_s: float = 0.0           # summed serve_once seconds (serving)
    traced: object = None               # trace.Trace
    summary: dict = field(default_factory=dict)   # trace.reduce(...)
    checks: list = field(default_factory=list)
    attempted: int = 0
    failed: int = 0
    compiles_in_window: int = 0
    device: dict = field(default_factory=dict)

    def spans_named(self, name, lo=None, hi=None):
        lo = self.w0 if lo is None else lo
        hi = self.w1 if hi is None else hi
        return [(a, b) for n, a, b in self.spans if n == name and a >= lo and a < hi]


class Spans:
    """Host spans around calls into the program's layers, kept in memory
    and, in a traced run, written into the profiler's trace."""

    def __init__(self, run: Run) -> None:
        self.run = run
        if run.trace:
            from jax.profiler import TraceAnnotation
            self._ann = TraceAnnotation
        else:
            self._ann = None

    def span(self, name):
        return _Span(self, name)

    def wrap(self, obj, attr: str, name: str) -> None:
        fn = getattr(obj, attr)

        def wrapped(*a, **k):
            with self.span(name):
                return fn(*a, **k)

        setattr(obj, attr, wrapped)


class _Span:
    def __init__(self, spans: Spans, name: str) -> None:
        self.spans, self.name = spans, name

    def __enter__(self):
        self.ann = self.spans._ann(self.name) if self.spans._ann else None
        if self.ann:
            self.ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc):
        self.t1 = time.monotonic()
        if self.ann:
            self.ann.__exit__(*exc)
        self.spans.run.spans.append((self.name, self.t0, self.t1))
        return False


class Children:
    """The broker and the generator, spawned; stopped and joined at exit."""

    def __init__(self, run_dir: Path) -> None:
        self.ctx = mp.get_context("spawn")
        self.run_dir = run_dir
        self.procs = []
        from repro.core.transport import serve_store
        self.broker_conn, child = self.ctx.Pipe()
        p = self.ctx.Process(target=serve_store, name="broker",
                             args=(str(run_dir / "broker"), child), daemon=True)
        p.start()
        self.procs.append(p)
        self.address = self._answer(self.broker_conn, p)
        self.gen_conn = None

    @staticmethod
    def _answer(conn, proc):
        """The child's first message, or an error if it dies or is silent."""
        deadline = time.monotonic() + START_TIMEOUT_S
        while not conn.poll(1.0):
            if not proc.is_alive() or time.monotonic() > deadline:
                raise RuntimeError(f"{proc.name} did not start "
                                   f"(exit code {proc.exitcode})")
        return conn.recv()

    def start_generator(self, spec: dict) -> None:
        from . import generator
        self.gen_conn, child = self.ctx.Pipe()
        p = self.ctx.Process(target=generator.main, name="generator",
                             args=(spec, child), daemon=True)
        p.start()
        self.procs.append(p)
        if self._answer(self.gen_conn, p) != "ready":
            raise RuntimeError("generator did not start")

    def go(self, t: float) -> None:
        self.gen_conn.send({"go": t})

    def stop_generator(self, wait_ids=(), timeout: float = 0.0) -> dict:
        self.gen_conn.send({"stop": list(wait_ids), "timeout": timeout})
        if not self.gen_conn.poll(timeout + STOP_TIMEOUT_S):
            raise RuntimeError("generator did not answer the stop")
        return self.gen_conn.recv()

    def close(self) -> None:
        try:
            self.broker_conn.send("stop")
        except (BrokenPipeError, OSError):
            pass
        for p in reversed(self.procs):
            p.join(timeout=STOP_TIMEOUT_S)
            if p.is_alive():
                p.kill()
                p.join(timeout=5)


class CompileCounter:
    """Counts XLA compilations (or cache loads) while ``armed``."""

    def __init__(self) -> None:
        import jax
        self.count, self.armed = 0, False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event == "/jax/core/compile/backend_compile_duration":
            self.count += 1


def device_info(devs, chips: int) -> dict:
    peak = 0
    for d in devs[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips, "memory_peak_bytes": peak}


def load_trace(run: Run, directory: str, names) -> None:
    """Read the run's profiler trace and reduce it over the window. Off
    the TPU (CPU rehearsals) the XLA client's threads stand for the
    device."""
    from . import trace
    kw = ({} if run.device["platform"] == "tpu" else
          {"device_plane": "/host:CPU", "op_line": "tf_XLAPjRtCpuClient"})
    run.traced = trace.load(directory, set(names) | {trace.WINDOW}, **kw)
    run.summary = trace.reduce(run.traced)


def note(line: str) -> None:
    """A line of the run's report on stderr."""
    print(line, file=sys.stderr, flush=True)


def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             t_start: float, *, cfg=None, require_tpu: bool = True,
             fault=None, run_dir: Path = RUN_DIR,
             compile_cache: bool = True) -> dict:
    """Run ``cell`` once and return the result object. The CPU tests pass
    ``cfg`` (a small configuration), ``require_tpu=False``, a ``run_dir``
    of their own, no compile cache, and ``fault``: a callable that breaks
    the timed path."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        raise SystemExit(f"{cell.chips} TPU chip(s) needed; JAX has "
                         f"{len(devs)} {devs[0].platform} device(s)")
    if compile_cache:
        use_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    from . import flops
    run = Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
              t_start=t_start, chips=cell.chips)
    run.cfg = cfg or cells.arch_config(cell.config)
    run.peak = (flops.peaks(devs[0].device_kind) if require_tpu
                else {"bf16_flops_per_s": float("nan")})
    shutil.rmtree(run_dir, ignore_errors=True)
    run_dir.mkdir(parents=True)
    counter = CompileCounter()
    children = Children(run_dir)
    try:
        # the traffic's kind names the driver: ``train.py``, ``serve.py``
        driver = importlib.import_module(f"{__package__}.{cell.traffic['kind']}")
        driver.run(run, children, counter, devs, fault)
    finally:
        children.close()
        gc.collect()
        shutil.rmtree(run_dir, ignore_errors=True)
    return result(run)


def result(run: Run) -> dict:
    metrics = {}
    wanted = run.cell.per_layer if run.trace else run.cell.end_to_end
    for m in wanted:
        v = cells.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    out = {"correct": all(c.ok for c in run.checks) and bool(run.checks),
           "attempted": run.attempted, "failed": run.failed,
           "metrics": metrics, "device": dict(run.device)}
    if run.trace:
        out["device"]["busy_s"] = run.summary["busy_s"]
        out["device"]["window_s"] = run.summary["window_s"]
        out["breakdown"] = {"device_ops": run.summary["device_ops"],
                            "idle_gaps": run.summary["idle_gaps"]}
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in run.checks}
    return out
