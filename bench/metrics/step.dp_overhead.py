"""DP overhead, the paper's quantity: device busy time per private step
over device busy time per non-private step (same model, shapes and AdamW
update, ``non_dp_gradient``), both read from one trace by the program each
step ran."""


def read(ctx):
    private = ctx.trace.busy_per_run("private", ctx.steps)
    plain = ctx.trace.busy_per_run("nonprivate", ctx.trace.nonprivate_steps)
    if not private or not plain:
        return None
    return private / plain
