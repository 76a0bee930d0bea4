"""1 - (union of device operation intervals / traced window), averaged over
the cell's devices.  Nothing to read where the trace holds no device
operation (a trace taken without a TPU)."""


def read(ctx):
    if ctx.trace.window_s <= 0 or not ctx.trace.ops:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
