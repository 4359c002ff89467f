"""Host cost of one telemetry span (enter + exit), in microseconds.

  PYTHONPATH=src python benchmarks/bench_spans.py

Three cases: a tracer with no annotation factory; the process tracer as the
runtime sets it up (``repro.runtime.tracing.install``) with no profiler
session; and the same inside a ``jax.profiler`` session, where each span
also writes a ``TraceAnnotation``. Each figure is the least of several
repeats, so that a busy host reads its quietest moment.
"""
from __future__ import annotations

import sys
import tempfile
import timeit
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

N = 100_000
REPEATS = 15


def per_span_us(tracer, n: int = N, repeats: int = REPEATS) -> float:
    def one():
        with tracer.span("bench/span"):
            pass
    return min(timeit.timeit(one, number=n) for _ in range(repeats)) / n * 1e6


def main() -> None:
    from repro.core.telemetry import Tracer, tracer
    print(f"no annotation factory: {per_span_us(Tracer(capacity=N))} us")
    import jax
    from repro.runtime import tracing
    tracing.install()
    print(f"installed, no profiler session: {per_span_us(tracer())} us")
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            # fewer spans: the session keeps every annotation in memory
            us = per_span_us(tracer(), n=N // 10, repeats=5)
            print(f"installed, profiler session: {us} us")
        finally:
            jax.profiler.stop_trace()


if __name__ == "__main__":
    main()
