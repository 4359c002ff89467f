"""Per-layer readings from the program's own instrumentation: host seconds
of its spans (``repro.core.telemetry``) and device seconds of the model's
named scopes (``repro.runtime.op_scopes``), per window step.

A program without that instrumentation gives nothing to read: every
function here then returns None and raises nothing.
"""
from __future__ import annotations

from collections import defaultdict

from . import trace
from .harness import note

PROGRAM = "train_step"          # the trainer's registered step
COVERAGE = 0.99                 # least share of leaf-op time the table must name
NOT_IN_TABLE = "(not in table)"


def span_s_per_step(run, name: str) -> float | None:
    """Seconds of the program's spans ``name`` that start inside the
    window, per window step; None if the program records no such span or
    its ring dropped spans that may have started in the window."""
    steps = run.work.get("steps")
    if not steps:
        return None
    try:
        from repro.core.telemetry import tracer
    except ImportError:
        return None
    tr = tracer()
    if tr.dropped and tr.dropped_until >= run.w0:
        return None
    recs = tr.spans(name)
    if not recs:
        return None
    return sum(r.t1 - r.t0 for r in recs if run.w0 <= r.t0 < run.w1) / steps


def device_s_per_step(run, scope: str) -> float | None:
    """Device seconds of the window's leaf ops in ``scope``, per step."""
    table = per_scope(run)
    return None if table is None else table.get(scope, 0.0)


def per_scope(run) -> dict | None:
    """``{scope: device seconds per window step}`` over the window's leaf
    device ops, computed once per run and printed whole; None when the
    program has no op table, or names less than ``COVERAGE`` of the leaf-op
    time (a stale table must not misattribute time)."""
    cached = getattr(run, "_scope_s_per_step", False)
    if cached is False:
        cached = _reduce(run)
        run._scope_s_per_step = cached
    return cached


def _op_table():
    try:
        from repro.runtime import op_scopes
    except ImportError:
        return None
    try:
        return op_scopes(PROGRAM)
    except KeyError:
        return None


def _mixed_table() -> dict:
    """``{op: scopes}`` of the step's ops whose fused members come from
    more than one scope; empty where the program cannot say."""
    try:
        from repro.runtime.tracing import op_mixed_scopes
        return op_mixed_scopes(PROGRAM)
    except (ImportError, KeyError):
        return {}


def _reduce(run) -> dict | None:
    steps = run.work.get("steps")
    if not steps or run.traced is None:
        return None
    table = _op_table()
    if table is None:
        return None
    lo, hi = trace.window(run.traced)
    chips = len(run.traced.ops)
    by_op = defaultdict(float)              # (scope, op) -> seconds
    for ops in run.traced.ops.values():
        for name, s, e in trace.leaves(ops):
            d = max(0.0, min(e, hi) - max(s, lo)) * 1e-9 / chips
            if d > 0:
                op = trace.op_name(name)
                by_op[table.get(op, NOT_IN_TABLE), op] += d
    secs = defaultdict(float)
    for (scope, _), v in by_op.items():
        secs[scope] += v
    total = sum(secs.values())
    named = total - secs.get(NOT_IN_TABLE, 0.0)
    share = named / total if total > 0 else 0.0
    note(f"device seconds per step by scope ({PROGRAM}, {steps} steps, "
         f"{share * 100}% of {total / steps} s of leaf ops named):")
    ranked = sorted(by_op.items(), key=lambda kv: -kv[1])
    for scope, v in sorted(secs.items(), key=lambda kv: -kv[1]):
        top = ", ".join(f"{op} {t / steps}" for (sc, op), t in ranked
                        if sc == scope)
        note(f"  {scope}: {v / steps} (top ops: {top[:300]})")
    for (scope, op), v in ranked:
        if scope in (NOT_IN_TABLE, "unscoped") and v >= 0.01 * total:
            note(f"  over 1% and not under a scope: {scope} {op}: {v / steps}")
    _note_mixed(ranked, total, steps)
    if share < COVERAGE:
        return None
    return {k: v / steps for k, v in secs.items() if k != NOT_IN_TABLE}


def _note_mixed(ranked, total: float, steps: int) -> None:
    """Print the share of leaf-op seconds in ops whose fused members come
    from more than one scope (each is credited whole to one), grouped by
    the scope credited and the scopes of its members."""
    mixed = _mixed_table()
    groups = defaultdict(lambda: [0.0, []])
    for (scope, op), v in ranked:
        members = mixed.get(op)
        if members:
            g = groups[scope, members]
            g[0] += v
            g[1].append(op)
    if not groups or total <= 0:
        return
    share = sum(g[0] for g in groups.values()) / total
    note(f"  ops whose fused members come from more than one scope: "
         f"{share * 100}% of leaf-op seconds")
    for (scope, members), (v, ops) in sorted(groups.items(),
                                             key=lambda kv: -kv[1][0]):
        note(f"    credited to {scope}, members {'+'.join(members)}: "
             f"{v / steps} s a step, {v / total * 100}% "
             f"({len(ops)} ops: {', '.join(ops[:4])})")
