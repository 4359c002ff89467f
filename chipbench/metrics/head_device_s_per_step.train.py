"""Model step: device seconds of the window's leaf ops in the ``head`` named
scope (the final norm, the vocabulary projection and the cross-entropy
loss), per window step; see ``scopes.per_scope``."""
from chipbench import scopes


def read(run):
    return scopes.device_s_per_step(run, "head")
