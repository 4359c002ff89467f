"""Generated tokens of the completions that landed on ``completions`` from
the window's whole batches, over the window."""
from chipbench import stats


def read(run):
    if run.cell.traffic["kind"] != "serve":
        return None
    return stats.window_rate(run.work["tokens"], run.w0, run.w1)
