"""Median host time of the program's own ``engine.private_step`` span in
the window: the engine's Python from entry to return, its noise key and
the dispatch of the compiled step included, the benchmark's timer and
feed not.  Nothing to read where the trace holds no such span."""
from bench import scopes


def read(ctx):
    return scopes.median_span_ms(ctx, "engine.private_step")
