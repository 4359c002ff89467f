"""Model step: device seconds of the window's leaf ops in the ``optimizer``
named scope (AdamW with gradient clipping), per window step; see
``scopes.per_scope``."""
from chipbench import scopes


def read(run):
    return scopes.device_s_per_step(run, "optimizer")
