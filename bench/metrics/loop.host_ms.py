"""Median host time of one ``private_step`` call until it returns: the
engine's Python, its noise-key derivation and the dispatch of the compiled
step.  The batch's transfer to the devices, before the call, is not in
it."""
import statistics


def read(ctx):
    if not ctx.dispatch_s:
        return None
    return 1e3 * statistics.median(ctx.dispatch_s)
