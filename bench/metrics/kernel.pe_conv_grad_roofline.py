"""Share of the ``pe_conv_grad`` kernel's device time in the window that
its roofline accounts for: over every call of the kernel in the window,
the sum of max(FLOPs / peak FLOP/s, HBM bytes / peak HBM bytes/s) over
the sum of the calls' measured times, on every chip.  FLOPs and bytes come
from each call's shapes (``bench/flops/pe_conv_grad.py``; the padded taps
where space to depth feeds the kernel), the peaks from
``bench/peaks.json``: the bf16 rate, the type the kernel multiplies in at
the default matmul precision.  Nothing to read where no call of the
kernel ran in the window, or without the chip's peaks."""
from bench.flops import pe_conv_grad


def read(ctx):
    peak_flops = ctx.peaks.get("bf16_flops_per_s")
    peak_bytes = ctx.peaks.get("hbm_bytes_per_s")
    w = ctx.trace.window
    if not peak_flops or not peak_bytes or not w:
        return None
    calls = {}
    busy = ideal = 0.0
    for o in ctx.trace.ops:
        if o.name not in calls:
            calls[o.name] = pe_conv_grad.parse(o.name)
        call = calls[o.name]
        start, end = max(o.start, w.start), min(o.end, w.end)
        if call is None or end <= start:
            continue
        busy += end - start
        ideal += (end - start) / (o.end - o.start) * max(
            call.flops / peak_flops, call.hbm_bytes / peak_bytes)
    return 100.0 * ideal / busy if busy > 0 else None
