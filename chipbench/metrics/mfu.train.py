"""Model FLOPs of the window's whole steps or batches over (window x chips x
bf16 peak); see ``stats.peak_share``."""
from chipbench import stats


def read(run):
    return stats.peak_share(run, run.w1 - run.w0)
