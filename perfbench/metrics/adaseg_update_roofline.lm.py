"""The fused AdaSEG update kernels' share of their HBM roofline: the least
bytes of the window's local steps (``counts.adaseg_update_bytes`` per
worker and step) over the HBM peak times the kernels' summed device
time."""
from perfbench import traceio


def read(ctx):
    secs = sum(traceio.op_seconds(ctx.trace, d, traceio.is_adaseg_update)
               for d in ctx.device_ids)
    if secs <= 0.0:
        return None
    steps = ctx.cell.work["worker_steps"] * ctx.window.rounds
    need = ctx.cell.counts["update_bytes_per_worker_step"] * steps
    return 100.0 * need / (ctx.peaks["hbm_bytes_per_s"] * secs)
