"""Set-up: process start to the first timed round (build, compile or
load from the cache, the three checked rounds)."""


def read(run):
    return run.setup_s
