"""Model step: device seconds of the window's leaf ops in the ``layer_stack``
named scope (the layer scan's own work: slicing the stacked weights,
stacking the saved carries, the block norms and residuals), per window step;
see ``scopes.per_scope``."""
from chipbench import scopes


def read(run):
    return scopes.device_s_per_step(run, "layer_stack")
