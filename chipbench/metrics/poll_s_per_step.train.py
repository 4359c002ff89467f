"""Log + transport: host seconds in the loader's ``consumer.poll`` calls
(the broker round trips), from the program's ``loader/poll`` spans that
start in the window, per window step."""
from chipbench import scopes


def read(run):
    return scopes.span_s_per_step(run, "loader/poll")
