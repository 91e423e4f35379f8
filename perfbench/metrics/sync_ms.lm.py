"""Device time per round of the operations under the engine's ``sync``
scope (uplink codec, merge, broadcast)."""
from perfbench import traceio


def read(ctx):
    return traceio.scope_ms_per_round(ctx, "sync")
