"""Device time per round of attention inside the local step: ops under
the model's ``attention`` scope (the Pallas forward, its recompute and the
backward; not the projections), mean over devices."""
from perfbench import scopes

LAYER = "attention"       # its scope inside ``local-compute``


def read(ctx):
    return scopes.layer_ms_per_round(ctx, LAYER)
