"""Device time per round of the all-reduce operations (the shard_map
round's psum across chips), mean over devices."""
from perfbench import traceio


def read(ctx):
    return traceio.ms_per_round(ctx, traceio.is_all_reduce)
