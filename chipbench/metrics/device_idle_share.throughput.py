"""Device: 1 - (union of device-operation intervals in the profiler trace /
the traced window), averaged over the chips (``trace.reduce``), in percent."""


def read(run):
    share = run.summary.get("idle_share") if run.summary else None
    return None if share is None else 100.0 * share
