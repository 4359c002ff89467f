"""Model FLOPs of the window's batches over (the summed ``serve_once``
seconds of those batches x bf16 peak): the chip's use while it serves,
with arrival gaps left out; see ``stats.peak_share``."""
from chipbench import stats


def read(run):
    return stats.peak_share(run, run.busy_flops_s)
