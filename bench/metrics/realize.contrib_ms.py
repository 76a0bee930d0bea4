"""Device ms per private step in every group's contribution to the clipped
sum: weighted sums of stashed per-example grads, book-keeping
contractions and the shared weighted backward: the union of the
intervals of the operations under the program's scope ``dp.contrib``,
averaged over the cell's devices (``bench/scopes.py``). Nothing to read
where the trace carries no scopes or none of its operations ran in the
window."""
from bench import scopes


def read(ctx):
    return scopes.read_ms(ctx, "dp.contrib")
