"""Device time per round of the operations under the engine's
``local-compute`` scope (the K local steps: oracle calls and update)."""
from perfbench import traceio


def read(ctx):
    return traceio.scope_ms_per_round(ctx, "local-compute")
