"""Device time of the layers the program names inside its local step.

An op belongs to a layer when its scope path holds the engine's
``local-compute`` and the layer's own ``jax.named_scope``. Under a
transformation the program's scope shows wrapped, as
``vmap(adaseg-update)`` or ``transpose(jvp(vocab))``, and still counts; a
``jit(...)`` of the same name is a function of that name, not the scope,
and does not (the Pallas attention forward is ``jit(attention)`` with or
without the scope). A trace recorded before the scopes existed reads None.
"""
from __future__ import annotations

import re

from perfbench import traceio

LOCAL = "local-compute"


def in_layer(name: str):
    """Ops inside ``local-compute`` whose scope path holds ``name`` as a
    component, bare or inside transformation wrappers other than ``jit``."""
    pat = re.compile(r"(^|/)(?:(?!p?jit\()[\w.-]+\()*"
                     rf"{re.escape(name)}\)*(/|$)")
    local = traceio.in_scope(LOCAL)
    return lambda o: local(o) and bool(pat.search(o.scope))


def layer_ms_per_round(ctx, name: str) -> float | None:
    """Device milliseconds per round of the layer ``name`` inside the local
    step, mean over the cell's devices; None where no op carries it."""
    return traceio.ms_per_round(ctx, in_layer(name))
