"""Share of the window during which a collective runs on a device and no
other operation does, averaged over the cell's devices.  Nothing to read
where the step holds no collective."""


def read(ctx):
    share = ctx.trace.exposed_collective_share()
    return None if share is None else 100.0 * share
