"""The 50th percentile of request latency, exact over all requests due in
the window (stats.latencies)."""
from chipbench import stats


def read(run):
    return stats.percentile(stats.latencies(run), 50)
