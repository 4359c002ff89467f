"""Serve runtime: the median host seconds of a ``serve_once`` call that
served a batch in the window (poll, prefill, per-token decode with its
host syncs, publish, commit)."""
from chipbench import stats


def read(run):
    return stats.percentile(run.work.get("batch_s", []), 50)
