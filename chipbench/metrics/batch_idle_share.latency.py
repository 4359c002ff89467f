"""Device idle share inside the union of the ``serve_once`` spans that
served a batch (those holding a ``prefill``), in percent: arrival gaps,
set by the rate, are left out."""


def read(run):
    if run.traced is None:
        return None
    from chipbench import trace
    lo, hi = trace.window(run.traced)
    share = trace.idle_within(run.traced, "serve_once", lo, hi, containing="prefill")
    return None if share is None else 100.0 * share
