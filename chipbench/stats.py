"""Arithmetic the metric readers share: exact percentiles, request
latencies and the share of the chip's peak."""
from __future__ import annotations

import math

import numpy as np


def percentile(values, q: float):
    """Exact percentile over all samples (linear between closest ranks);
    None for no samples."""
    if not len(values):
        return None
    v = float(np.percentile(np.asarray(values, np.float64), q))
    return v if math.isfinite(v) else None


def latencies(run):
    """From each request's due time at the generator to its completion's
    arrival at the collector, over every request due in the window. A
    request that never landed counts at the time the drain gave up, a
    lower bound of its latency."""
    if run.cell.traffic.get("judged_by") != "latency" or not run.requests:
        return []
    gave_up = run.w1 + run.cell.traffic["drain_s"]
    return [(r["arrived"] if r["arrived"] is not None else gave_up) - r["due"]
            for r in run.requests.values()]


def window_rate(work: float, w0: float, w1: float) -> float:
    """Work of the window's whole steps or batches over the window."""
    return work / (w1 - w0)


def peak_share(run, seconds: float):
    """Model FLOPs of the window (``flops.py``: real tokens, matmuls and
    attended pairs, training 3x, no recomputation) over seconds x chips x
    the chip's bf16 peak (``peaks.json``), in percent."""
    peak = run.peak.get("bf16_flops_per_s", float("nan"))
    if not run.window_flops or not seconds or not math.isfinite(peak):
        return None
    return 100.0 * run.window_flops / (seconds * run.chips * peak)
