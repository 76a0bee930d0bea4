"""Device ms per private step in the norm phase of every group realized per
example (``pe``: grouped convolutions, ``core/kinds.py``), the
squared-norm reduction and the per-example grads it stashes included:
the union of the intervals of the operations under the program's scope
``dp.norm/pe``, averaged over the cell's devices (``bench/scopes.py``).
Nothing to read where the trace carries no scopes or none of its
operations ran in the window."""
from bench import scopes


def read(ctx):
    return scopes.read_ms(ctx, "dp.norm/pe")
