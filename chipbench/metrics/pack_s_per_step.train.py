"""Loader: host seconds tokenizing and packing polled records into rows and
stacking the batch, from the program's ``loader/pack`` spans that start in
the window, per window step."""
from chipbench import scopes


def read(run):
    return scopes.span_s_per_step(run, "loader/pack")
