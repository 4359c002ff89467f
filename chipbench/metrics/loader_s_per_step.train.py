"""Loader: host seconds inside ``loader.next_batch`` per window step
(poll, tokenize, pack), from the harness's span around the call."""


def read(run):
    steps = run.work.get("steps")
    if not steps:
        return None
    return sum(b - a for a, b in run.spans_named("next_batch")) / steps
