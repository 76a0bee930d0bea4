"""Share of the window's device busy time spent in operations that
produce per-example convolution weight gradients: the grouped-convolution
realization (``pe``) of ``core/kinds.py``.  The TPU compiler rewrites the
grouped convolution into a batched one fused with its squared norm, so an
operation is counted by its result: one that holds as many elements as the
batch on the device times one convolution layer's weights (see
``bench/trace.py``).  Nothing to read where no layer is realized so."""


def read(ctx):
    share = ctx.trace.share_of("private", "pe_conv")
    return None if share is None else 100.0 * share
