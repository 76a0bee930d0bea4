"""Device ms per private step in the Gaussian noise draw and add
(``core/clipping.py`` ``add_noise``) and the optimizer update
(``core/engine.py``): the union of the intervals of the operations under
the program's scope ``dp.noise`` and ``dp.update``, averaged over the
cell's devices (``bench/scopes.py``). Nothing to read where the trace
carries no scopes or none of its operations ran in the window."""
from bench import scopes


def read(ctx):
    return scopes.read_ms(ctx, "dp.noise", "dp.update")
