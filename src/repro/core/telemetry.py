"""Latency histograms, a process-local metrics registry, and fabric-wide
telemetry aggregation (paper §IV.C: NiFi's status-history and provenance
views — "the amount of data read, written, in, and out in the last 5
minutes" — extended from *how many* to *how long* and *where time went*).

The paper's operational story has two halves this module serves:

* **status history** — per-component gauges over time. ``MetricsRegistry``
  unifies the repo's existing counter surfaces (``ComponentStats``,
  ``Connection.snapshot()``, acquisition connector gauges) with the new
  latency histograms behind one ``collect()``, rendered either as a
  Prometheus-style text exposition (``render_text()``) or a JSON dump.
* **provenance / lineage timing** — the flow engine samples records
  (``trace_sample_rate``) and stamps a ``trace.id`` attribute; per-hop
  span events ride the existing provenance repository so
  ``FlowGraph.trace_spans()`` can reconstruct a timed span tree for one
  record's ingest→land journey.

Design constraints, in order:

1. **Mergeable.** Histograms use *fixed* power-of-two bucket boundaries
   (bucket ``i`` covers ``[2**(i-1), 2**i)`` microseconds), so histograms
   recorded independently in N worker processes merge *exactly* — merge is
   element-wise addition, and percentiles over the merged histogram equal
   percentiles over a single histogram fed all samples. This is what lets
   fabric workers ship their histogram state on every heartbeat and the
   coordinator fold them into one fabric-wide view mid-run.
2. **Bounded.** A histogram is at most :data:`NBUCKETS` integers — memory
   does not grow with the number of observations, and the serialized form
   is sparse (only non-empty buckets travel on heartbeats).
3. **Cheap.** The hot path records one ``perf_counter`` pair per *batch*
   and folds the batch size in as a bucket weight, so per-record cost is
   amortized to ~zero. Everything here is optional: a ``FlowGraph`` built
   with ``telemetry=False`` carries no registry and the engine skips every
   hook.
4. **Deterministic under test.** Histograms, flight recorders, and
   ``WindowedCounter`` accept an injected ``clock`` so tests on a
   load-spiky 1-CPU host never sleep against real time.

``FlightRecorder`` keeps the last N status snapshots in a ring — the
post-mortem view dumped to JSON when a fabric worker dies or an acceptance
scenario fails, so a red run shows *where* depth/latency diverged instead
of a bare boolean.

``Tracer`` carries the same registry past the log to the device hop: timed
spans at step or batch granularity (loader, trainer) kept in a bounded
ring and folded into ``span_seconds{span=...}``, plus monotonic counters.
The process-wide one is reached through ``span()`` / ``count()`` /
``tracer()``. Its registry is what an operator scrapes: a fabric's
``render_metrics_text()`` (and so its ``serve_metrics()`` endpoint) ends
with it, and a process without a fabric, such as a trainer reading a
remote log, serves it with ``serve_scrape(tracer().registry.render_text)``.
Each span also opens an annotation from an installable factory: the
runtime installs ``jax.profiler.TraceAnnotation``, so under a profiler
session every span lands in the device trace on its clock, and this
module stays free of JAX.
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from collections import deque
from contextlib import contextmanager
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Iterable, Mapping, NamedTuple, Optional

__all__ = [
    "NBUCKETS", "LatencyHistogram", "MetricsRegistry", "FlightRecorder",
    "ScrapeServer", "serve_scrape", "metric_key", "split_metric_key",
    "merge_histogram_states", "summarize_histogram_state",
    "render_histogram_state_text", "Counter", "SpanRecord", "Tracer",
    "tracer", "span", "count",
]

#: Fixed bucket count. Bucket 0 holds sub-microsecond samples; bucket i
#: (i >= 1) covers [2**(i-1), 2**i) microseconds; the last bucket is a
#: catch-all. 2**62 µs is ~146k years — nothing a pipeline measures
#: overflows the range.
NBUCKETS = 64

#: Default summary quantiles (and their text-exposition labels).
_QUANTILES = ((0.5, "p50_ms"), (0.9, "p90_ms"), (0.99, "p99_ms"))


def bucket_index(seconds: float) -> int:
    """Bucket for a duration. Fixed boundaries — never configuration-
    dependent — so any two histograms merge exactly."""
    us = int(seconds * 1e6)
    if us <= 0:
        return 0
    return min(us.bit_length(), NBUCKETS - 1)


def _bucket_midpoint_sec(i: int) -> float:
    """Representative value for bucket ``i``: the geometric midpoint of
    its [2**(i-1), 2**i) µs range (0.5 µs for the sub-µs bucket)."""
    if i == 0:
        return 0.5e-6
    return (2.0 ** (i - 0.5)) / 1e6


class LatencyHistogram:
    """Thread-safe, mergeable, bounded-memory latency histogram.

    ``record(seconds, n)`` folds ``n`` observations of the same duration in
    at once — the flow engine times a *batch* and records with
    ``n=len(batch)``, amortizing the clock reads. ``merge`` is exact
    (fixed boundaries); ``percentile`` answers from bucket midpoints, so
    its error is bounded by the power-of-two bucket width (~±41%
    worst-case on an individual sample, far tighter on the aggregate —
    exactly the resolution regime of Prometheus/HDR-style log buckets).
    """

    __slots__ = ("_counts", "_count", "_sum", "_lock", "_clock")

    def __init__(self, clock: Optional[Callable[[], float]] = None) -> None:
        self._counts = [0] * NBUCKETS
        self._count = 0
        self._sum = 0.0
        self._lock = threading.Lock()
        self._clock = clock or time.perf_counter

    # -- recording -----------------------------------------------------------
    def record(self, seconds: float, n: int = 1) -> None:
        """Record ``n`` observations of ``seconds`` (batch-amortized)."""
        if n <= 0:
            return
        i = bucket_index(seconds)
        s = seconds * n
        with self._lock:
            self._counts[i] += n
            self._count += n
            self._sum += s

    def record_many(self, durations: Iterable[float]) -> None:
        """Record individually-measured durations under one lock hold."""
        add = [0] * NBUCKETS
        total = 0
        tsum = 0.0
        for d in durations:
            add[bucket_index(d)] += 1
            total += 1
            tsum += d
        if not total:
            return
        with self._lock:
            for i, c in enumerate(add):
                if c:
                    self._counts[i] += c
            self._count += total
            self._sum += tsum

    @contextmanager
    def timer(self, n: int = 1):
        """``with hist.timer(n=len(batch)):`` — one clock pair per block."""
        t0 = self._clock()
        try:
            yield
        finally:
            self.record(self._clock() - t0, n)

    # -- reading -------------------------------------------------------------
    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum_seconds(self) -> float:
        with self._lock:
            return self._sum

    def percentile(self, q: float) -> float:
        """q-th percentile in seconds (q in [0, 1]); 0.0 when empty."""
        if not 0.0 <= q <= 1.0:
            raise ValueError("quantile must be within [0, 1]")
        with self._lock:
            total = self._count
            counts = list(self._counts)
        if not total:
            return 0.0
        rank = q * total
        seen = 0
        for i, c in enumerate(counts):
            seen += c
            if seen >= rank and c:
                return _bucket_midpoint_sec(i)
        for i in range(NBUCKETS - 1, -1, -1):     # pragma: no cover — q=1.0
            if counts[i]:
                return _bucket_midpoint_sec(i)
        return 0.0

    def summary(self) -> dict:
        """Count, mean, and the standard quantiles in milliseconds."""
        with self._lock:
            total = self._count
            tsum = self._sum
        out = {"count": total,
               "mean_ms": round(tsum / total * 1e3, 3) if total else 0.0}
        for q, label in _QUANTILES:
            out[label] = round(self.percentile(q) * 1e3, 3)
        return out

    # -- merge / serialization ----------------------------------------------
    def merge(self, other: "LatencyHistogram") -> "LatencyHistogram":
        """Fold ``other`` into self (exact: fixed bucket boundaries)."""
        with other._lock:
            counts = list(other._counts)
            count = other._count
            tsum = other._sum
        with self._lock:
            for i, c in enumerate(counts):
                if c:
                    self._counts[i] += c
            self._count += count
            self._sum += tsum
        return self

    def to_dict(self) -> dict:
        """Sparse JSON-safe state: ``{"b": {bucket: count}, "n": ..., "s": ...}``."""
        with self._lock:
            return {
                "b": {str(i): c for i, c in enumerate(self._counts) if c},
                "n": self._count,
                "s": self._sum,
            }

    @classmethod
    def from_dict(cls, state: Mapping,
                  clock: Optional[Callable[[], float]] = None
                  ) -> "LatencyHistogram":
        h = cls(clock=clock)
        for i, c in (state.get("b") or {}).items():
            h._counts[int(i)] += int(c)
        h._count = int(state.get("n", 0))
        h._sum = float(state.get("s", 0.0))
        return h


# -- canonical metric keys ----------------------------------------------------
def metric_key(name: str, labels: Mapping[str, str] | None = None) -> str:
    """Canonical ``name{k="v",...}`` key (labels sorted) — both the registry
    index and the cross-worker merge key for serialized histogram state."""
    if not labels:
        return name
    lab = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return f"{name}{{{lab}}}"


def split_metric_key(key: str) -> tuple[str, str]:
    """``'a{x="1"}'`` -> ``('a', 'x="1"')``; label-less keys -> ``(key, '')``."""
    if "{" in key:
        name, rest = key.split("{", 1)
        return name, rest.rstrip("}")
    return key, ""


# -- serialized-state helpers (coordinator side) ------------------------------
def merge_histogram_states(into: dict, state: Mapping[str, Mapping]) -> dict:
    """Fold one serialized ``{key: hist.to_dict()}`` map into ``into``.
    Exact for the same reason instance merge is: fixed boundaries."""
    for key, hs in state.items():
        cur = into.get(key)
        if cur is None:
            into[key] = {"b": dict((hs.get("b") or {})),
                         "n": int(hs.get("n", 0)),
                         "s": float(hs.get("s", 0.0))}
            continue
        for i, c in (hs.get("b") or {}).items():
            cur["b"][i] = cur["b"].get(i, 0) + int(c)
        cur["n"] += int(hs.get("n", 0))
        cur["s"] += float(hs.get("s", 0.0))
    return into


def summarize_histogram_state(state: Mapping[str, Mapping]) -> dict:
    """``{key: summary}`` for a serialized state map (fabric ``status()``)."""
    return {key: LatencyHistogram.from_dict(hs).summary()
            for key, hs in state.items()}


def render_histogram_state_text(state: Mapping[str, Mapping],
                                prefix: str = "repro_") -> str:
    """Prometheus summary-style exposition for a serialized state map."""
    lines: list[str] = []
    for key in sorted(state):
        h = LatencyHistogram.from_dict(state[key])
        name, labels = split_metric_key(key)
        base = prefix + name
        for q, _ in _QUANTILES:
            qlab = f'quantile="{q}"'
            lab = f"{labels},{qlab}" if labels else qlab
            lines.append(f"{base}{{{lab}}} {h.percentile(q):.9f}")
        suffix = f"{{{labels}}}" if labels else ""
        lines.append(f"{base}_count{suffix} {h.count}")
        lines.append(f"{base}_sum{suffix} {h.sum_seconds:.9f}")
    return "\n".join(lines) + ("\n" if lines else "")


class Counter:
    """Monotonic counter; ``add`` is safe from any thread."""

    __slots__ = ("_value", "_lock")

    def __init__(self) -> None:
        self._value = 0
        self._lock = threading.Lock()

    def add(self, n: int = 1) -> None:
        with self._lock:
            self._value += n

    @property
    def value(self) -> int:
        return self._value


class MetricsRegistry:
    """Process-local metric surface: named+labelled latency histograms plus
    pluggable gauge *sources* (callables returning ``{instance: {field:
    value}}`` — the shape of ``ComponentStats.snapshot()``,
    ``Connection.snapshot()``, and the acquisition connector gauges), all
    behind one ``collect()`` / ``render_text()`` / ``to_json()``.

    ``histograms_state()`` is the fabric wire format: the canonical-key →
    sparse-dict map a worker ships on every heartbeat.
    """

    def __init__(self, clock: Optional[Callable[[], float]] = None) -> None:
        self._clock = clock
        self._lock = threading.Lock()
        self._hists: dict[str, LatencyHistogram] = {}
        self._counters: dict[str, Counter] = {}
        self._sources: dict[str, Callable[[], Mapping]] = {}

    # -- histograms ----------------------------------------------------------
    def histogram(self, name: str, **labels: str) -> LatencyHistogram:
        """Get-or-create the histogram for ``(name, labels)``."""
        key = metric_key(name, labels)
        with self._lock:
            h = self._hists.get(key)
            if h is None:
                h = self._hists[key] = LatencyHistogram(clock=self._clock)
            return h

    def histograms_state(self) -> dict:
        """Serialized ``{canonical key: hist.to_dict()}`` (heartbeat cargo)."""
        with self._lock:
            hists = list(self._hists.items())
        return {key: h.to_dict() for key, h in hists}

    def summaries(self) -> dict:
        """``{canonical key: summary}`` — the ``status()['telemetry']`` body."""
        with self._lock:
            hists = list(self._hists.items())
        return {key: h.summary() for key, h in hists}

    def merged(self, name: str) -> LatencyHistogram:
        """One histogram folding every label set of ``name`` together."""
        out = LatencyHistogram()
        with self._lock:
            hists = list(self._hists.items())
        for key, h in hists:
            if split_metric_key(key)[0] == name:
                out.merge(h)
        return out

    # -- counters ------------------------------------------------------------
    def counter(self, name: str, **labels: str) -> "Counter":
        """Get-or-create the monotonic counter for ``(name, labels)``."""
        key = metric_key(name, labels)
        with self._lock:
            c = self._counters.get(key)
            if c is None:
                c = self._counters[key] = Counter()
            return c

    def counters(self) -> dict:
        """``{canonical key: value}`` of every counter."""
        with self._lock:
            counters = list(self._counters.items())
        return {key: c.value for key, c in counters}

    # -- gauge sources -------------------------------------------------------
    def register_source(self, kind: str, fn: Callable[[], Mapping]) -> None:
        """Register a gauge source. ``fn()`` must return ``{instance:
        {field: value}}``; non-numeric fields are skipped at render time.
        ``kind`` becomes the instance label name (e.g. ``processor``)."""
        with self._lock:
            self._sources[kind] = fn

    # -- collection ----------------------------------------------------------
    def collect(self) -> dict:
        """One unified snapshot: every gauge source plus every histogram."""
        with self._lock:
            sources = list(self._sources.items())
        gauges = {}
        for kind, fn in sources:
            try:
                gauges[kind] = {str(k): dict(v) for k, v in fn().items()}
            except Exception:           # a dying component must not kill scrape
                gauges[kind] = {}
        return {"gauges": gauges, "counters": self.counters(),
                "histograms": self.summaries()}

    def render_text(self, prefix: str = "repro_") -> str:
        """Prometheus-style text exposition of ``collect()``."""
        snap = self.collect()
        lines: list[str] = []
        for kind in sorted(snap["gauges"]):
            for inst in sorted(snap["gauges"][kind]):
                fields = snap["gauges"][kind][inst]
                for field in sorted(fields):
                    v = fields[field]
                    if isinstance(v, bool) or not isinstance(v, (int, float)):
                        continue
                    lines.append(
                        f'{prefix}{kind}_{field}{{{kind}="{inst}"}} {v}')
        for key in sorted(snap["counters"]):
            name, labels = split_metric_key(key)
            suffix = f"{{{labels}}}" if labels else ""
            lines.append(f"{prefix}{name}_total{suffix} {snap['counters'][key]}")
        text = "\n".join(lines) + ("\n" if lines else "")
        return text + render_histogram_state_text(
            self.histograms_state(), prefix=prefix)

    def to_json(self) -> str:
        return json.dumps(self.collect(), sort_keys=True, default=str)


class FlightRecorder:
    """Bounded ring of the last N status snapshots — the post-mortem a
    worker death or failed acceptance scenario dumps to JSON, so a red run
    shows where queue depth / latency / watermarks diverged over the final
    seconds instead of one boolean."""

    def __init__(self, capacity: int = 64,
                 clock: Optional[Callable[[], float]] = None) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.capacity = capacity
        self._clock = clock or time.time
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()

    def record(self, snapshot: Mapping) -> None:
        with self._lock:
            self._ring.append({"ts": self._clock(), "status": snapshot})

    def snapshots(self) -> list:
        with self._lock:
            return list(self._ring)

    def dump_json(self) -> str:
        return json.dumps(self.snapshots(), sort_keys=True, default=str)

    def dump(self, path) -> str:
        """Write the ring to ``path`` (JSON); returns the path as str."""
        data = self.dump_json()
        with open(path, "w", encoding="utf-8") as f:
            f.write(data)
        return str(path)


# -- spans and counters -------------------------------------------------------
class SpanRecord(NamedTuple):
    """One finished span. ``t0``/``t1`` are on the tracer's clock
    (``time.monotonic`` by default); ``parent_id`` is the span that was open
    on the same thread; ``trace_id`` is the caller's (a step index) or the
    parent's."""
    id: int
    parent_id: Optional[int]
    name: str
    trace_id: Optional[int]
    t0: float
    t1: float


class Tracer:
    """Spans and counters over one ``MetricsRegistry``.

    ``span(name, trace_id=None)`` times a block with one clock pair and
    keeps a ``SpanRecord`` in a ring of ``capacity`` (``dropped`` counts
    what it pushed out, ``dropped_until`` the latest end among those), and
    records the duration in ``span_seconds{span=<name>}``. ``count(name,
    n, **labels)`` adds to the registry's counter ``(name, labels)``. Meant
    for step or batch granularity: a span costs a clock pair, a ring append
    and a histogram record, never work per record.

    The program's counters: ``loader_*`` (``data/loader.py``), ``compiles``
    (``runtime/tracing.py``) and ``ssd_path{path=kernel|chunked}``, one
    count per trace of the SSD op by the path it compiled
    (``kernels/ssd/ops.py``).

    ``set_annotation(factory)``: each span also enters ``factory(name)``
    unless it returns None: a context manager such as
    ``jax.profiler.TraceAnnotation``, so that a profiler session records
    the span on the device trace's clock.
    """

    def __init__(self, registry: Optional[MetricsRegistry] = None,
                 capacity: int = 65536,
                 clock: Optional[Callable[[], float]] = None) -> None:
        if capacity <= 0:
            raise ValueError("capacity must be positive")
        self.registry = registry if registry is not None else MetricsRegistry()
        self._clock = clock or time.monotonic
        self._ring: deque = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._ids = itertools.count(1)
        self._local = _OpenSpans()
        self._annotation: Optional[Callable[[str], object]] = None
        self.dropped = 0
        self.dropped_until = float("-inf")

    def set_annotation(self, factory: Optional[Callable[[str], object]]) -> None:
        """Install (or, with None, remove) the per-span annotation factory."""
        self._annotation = factory

    # -- recording -----------------------------------------------------------
    def span(self, name: str, trace_id: Optional[int] = None) -> "_Span":
        """``with tracer.span("train/step", trace_id=i):``"""
        return _Span(self, name, trace_id)

    def record(self, name: str, t0: float, t1: float,
               trace_id: Optional[int] = None) -> None:
        """Keep a span measured elsewhere (a back-dated compile), as a child
        of the span open on this thread."""
        stack = self._local.stack
        sp = _Span(self, name, trace_id)
        sp._parent = stack[-1]._id if stack else None
        if trace_id is None and stack:
            sp._trace_id = stack[-1]._trace_id
        sp._id, sp._t0 = next(self._ids), t0
        sp._finish(t1)

    def count(self, name: str, n: int = 1, **labels: str) -> None:
        """Add ``n`` to the registry's counter ``(name, labels)``."""
        self.registry.counter(name, **labels).add(n)

    # -- reading -------------------------------------------------------------
    def value(self, name: str, **labels: str) -> int:
        """The counter ``(name, labels)`` (0 before its first ``count``)."""
        return self.registry.counters().get(metric_key(name, labels), 0)

    def spans(self, name: Optional[str] = None) -> list[SpanRecord]:
        """The ring's records, oldest first (all, or those named ``name``)."""
        with self._lock:
            recs = list(self._ring)
        return [SpanRecord(*r) for r in recs if name is None or r[2] == name]

    def now(self) -> float:
        return self._clock()


class _OpenSpans(threading.local):
    """Per thread: the open spans, innermost last."""

    def __init__(self) -> None:
        self.stack: list = []


class _Span:
    __slots__ = ("_tracer", "_name", "_trace_id", "_id", "_parent",
                 "_stack", "_ann", "_t0")

    def __init__(self, tracer: Tracer, name: str,
                 trace_id: Optional[int]) -> None:
        self._tracer, self._name, self._trace_id = tracer, name, trace_id

    def __enter__(self) -> "_Span":
        tr = self._tracer
        stack = self._stack = tr._local.stack
        if stack:
            top = stack[-1]
            self._parent = top._id
            if self._trace_id is None:
                self._trace_id = top._trace_id
        else:
            self._parent = None
        self._id = next(tr._ids)
        stack.append(self)
        # the clock pair brackets the annotation: under a profiler session
        # a span's time includes what its annotation costs, so children's
        # times still add up to their parent's
        self._t0 = tr._clock()
        factory = tr._annotation
        ann = self._ann = factory(self._name) if factory is not None else None
        if ann is not None:
            ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        if self._ann is not None:
            self._ann.__exit__(*exc)
        t1 = self._tracer._clock()
        self._stack.pop()
        self._finish(t1)
        return False

    def _finish(self, t1: float) -> None:
        """Ring the record and record its duration in its histogram."""
        tr, name, t0 = self._tracer, self._name, self._t0
        with tr._lock:
            ring = tr._ring
            if len(ring) == ring.maxlen:
                tr.dropped += 1
                if ring[0][5] > tr.dropped_until:
                    tr.dropped_until = ring[0][5]
            ring.append((self._id, self._parent, name, self._trace_id, t0, t1))
        tr.registry.histogram("span_seconds", span=name).record(t1 - t0)


_TRACER = Tracer()


def tracer() -> Tracer:
    """The process-wide tracer that the loader and the runtime record to."""
    return _TRACER


#: ``span(name, trace_id=None)`` and ``count(name, n=1, **labels)`` on the
#: process-wide tracer (``Tracer.span``, ``Tracer.count``)
span = _TRACER.span
count = _TRACER.count


# -- scrape endpoint ----------------------------------------------------------
class ScrapeServer:
    """A tiny stdlib HTTP server exposing one text render at ``/metrics``
    (and ``/``). Daemon-threaded; ``close()`` is idempotent."""

    def __init__(self, render_fn: Callable[[], str], port: int = 0,
                 host: str = "127.0.0.1") -> None:
        outer = self

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self) -> None:           # noqa: N802 — stdlib API
                if self.path.split("?", 1)[0] not in ("/", "/metrics"):
                    self.send_error(404)
                    return
                try:
                    body = outer.render_fn().encode("utf-8")
                except Exception as e:      # noqa: BLE001 — scrape must answer
                    self.send_error(500, str(e))
                    return
                self.send_response(200)
                self.send_header("Content-Type",
                                 "text/plain; version=0.0.4; charset=utf-8")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a) -> None:  # silence per-request stderr
                pass

        self.render_fn = render_fn
        self._httpd = ThreadingHTTPServer((host, port), _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self.url = f"http://{host}:{self.port}/metrics"
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name=f"metrics-scrape-{self.port}",
            daemon=True)
        self._thread.start()
        self._closed = False

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=5.0)


def serve_scrape(render_fn: Callable[[], str], port: int = 0,
                 host: str = "127.0.0.1") -> ScrapeServer:
    """Start an HTTP scrape endpoint serving ``render_fn()`` at /metrics."""
    return ScrapeServer(render_fn, port=port, host=host)
