"""Model step: device seconds of the window's leaf ops in the ``ssd`` named
scope (the chunked SSD (`ssd_ops.ssd`) and its D skip, forward, backward and
recomputed), per window step; see ``scopes.per_scope``."""
from chipbench import scopes


def read(run):
    return scopes.device_s_per_step(run, "ssd")
