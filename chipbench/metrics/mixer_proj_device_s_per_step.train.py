"""Model step: device seconds of the window's leaf ops in the ``mixer_proj``
named scope (the Mamba mixer's input projections (`_project_streams`) and
output projection), per window step; see ``scopes.per_scope``."""
from chipbench import scopes


def read(run):
    return scopes.device_s_per_step(run, "mixer_proj")
