"""Model FLOPs utilization of the private step: the model FLOPs of the
samples the window completed, over (window x chips x the chip's bf16
peak).  Model FLOPs are 3 x the non-private forward, counted from the
configuration's shapes by ``bench/flops/<family>.py``; per-example norms
and contributions are DP overhead and do not count.  The peak is bf16
because the step runs float32 at the default matmul precision, one bf16
pass on the MXU."""
from bench import spec


def read(ctx):
    flops = spec.load_flops(ctx.cell.config)
    peak = ctx.peaks.get("bf16_flops_per_s")
    if flops is None or peak is None or ctx.window_s <= 0:
        return None
    done = flops.train_flops_per_sample(ctx.cell.config) * ctx.batch \
        * ctx.steps
    return 100.0 * done / (ctx.window_s * ctx.devices * peak)
