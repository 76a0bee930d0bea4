"""Device ms per private step in the norm phase of every group realized
rank-1 (dense layers, ``core/kinds.py``): the union of the intervals of
the operations under the program's scope ``dp.norm/rank1``, averaged
over the cell's devices (``bench/scopes.py``). Nothing to read where the
trace carries no scopes or none of its operations ran in the window."""
from bench import scopes


def read(ctx):
    return scopes.read_ms(ctx, "dp.norm/rank1")
