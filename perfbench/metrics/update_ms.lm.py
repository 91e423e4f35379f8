"""Device time per round of the AdaSEG update inside the local step: ops
under the ``adaseg-update`` scope (the explore and anchor kernels, their
slab relayouts, the η/Z² bookkeeping and the ``enabled`` masks), mean over
devices. The update kernels' roofline share counts the kernels alone."""
from perfbench import scopes

LAYER = "adaseg-update"       # its scope inside ``local-compute``


def read(ctx):
    return scopes.layer_ms_per_round(ctx, LAYER)
