"""Tokens trained in the window's whole steps (batch x seq each) over the
window, from block_until_ready to block_until_ready."""
from chipbench import stats


def read(run):
    if run.cell.traffic["kind"] != "train":
        return None
    return stats.window_rate(run.work["tokens"], run.w0, run.w1)
