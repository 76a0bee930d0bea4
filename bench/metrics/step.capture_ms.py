"""Device ms per private step in the forward and backward with taps
(``core/tapper.py`` ``capture_backward``): the union of the intervals of
the operations under the program's scope ``dp.capture``, averaged over
the cell's devices (``bench/scopes.py``). Nothing to read where the
trace carries no scopes or none of its operations ran in the window."""
from bench import scopes


def read(ctx):
    return scopes.read_ms(ctx, "dp.capture")
