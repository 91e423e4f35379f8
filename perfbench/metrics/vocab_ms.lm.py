"""Device time per round of the vocabulary-wide work inside the local
step: ops under the model's ``vocab`` scope (the embedding lookup, the
head, its log-softmax and the NLL gather, forward and backward), mean over
devices."""
from perfbench import scopes

LAYER = "vocab"       # its scope inside ``local-compute``


def read(ctx):
    return scopes.layer_ms_per_round(ctx, LAYER)
