"""Run one cell as ``bench/run.py --trace 1`` does, and read the program's
own scopes and spans from the trace as well.

    python3 bench/profile_cell.py --workload <name> --seed <n> \
        --seconds <s> [--out <file.json>]

The run is ``run.run`` with its trace read by ``bench/scopes.py``: the
listed per-layer readers read on that ``ScopedTrace`` what they read on
``bench/trace.py``'s ``Trace``, and the run prints the same last line as
``bench/run.py``.  Then one more JSON line, also written to ``--out``:

- ``window``: steps, samples/s and the p90 step of the traced window;
- ``scope_metrics``: the readers of the program's scopes and spans
  (``SCOPE_READERS``, files in ``bench/metrics``);
- ``coverage``: the share of the window's device busy time under some
  ``dp.*`` scope; ``scopes``: device ms per private step of each scope;
- ``agree``: ``realize.pe_ms`` beside ``realize.grouped_conv_share`` times
  the busy ms per private step, and ``engine.host_ms`` beside
  ``loop.host_ms``;
- ``planner``: per layer, the plan's norm and sum realization and
  predicted MFLOP per device (``engine.explain()``), the measured ms per
  private step of its ``dp.norm`` and ``dp.contrib`` scopes, and the
  TFLOP/s that the predicted FLOPs give over them;
- ``breakdown``: the scoped breakdown (``ScopedTrace.breakdown``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from bench import run, scopes, spec  # noqa: E402
from bench import trace as tracemod  # noqa: E402

SCOPE_READERS = ("step.capture_ms", "realize.pe_ms", "realize.ghost_ms",
                 "realize.rank1_ms", "realize.contrib_ms", "noise_opt.ms",
                 "engine.host_ms")


def planner_table(plan, tr: scopes.ScopedTrace, steps: int) -> list:
    rows = []
    for g in plan.groups:
        group = "/".join(map(str, g.path)).replace("/", ".")
        contrib = ("dp.contrib/backward" if g.sum_method == "backward"
                   else f"dp.contrib/{g.sum_method}/{group}")
        for n in g.members:
            lp = plan.layers[n]
            norm = f"dp.norm/{lp.norm_method}/{group}"
            sum_flops = (lp.wgrad_flops if g.sum_method == "backward"
                         else lp.contrib_flops)
            norm_ms = tr.scope_ms(norm, steps)
            sum_ms = tr.scope_ms(contrib, steps)
            rows.append({
                "layer": n, "norm": lp.norm_method, "sum": g.sum_method,
                "norm_mflop": lp.norm_flops / 1e6,
                "sum_mflop": sum_flops / 1e6,
                "norm_ms": norm_ms, "sum_ms": sum_ms,
                "norm_tflops": (lp.norm_flops / norm_ms / 1e9
                                if norm_ms else None),
                "sum_tflops": (sum_flops / sum_ms / 1e9
                               if sum_ms and g.sum_method != "backward"
                               else None)})
    return rows


def profile(cell: spec.Cell, seed: int, seconds: float, *,
            require_tpu: bool = True, root: pathlib.Path = spec.ROOT):
    """``(result, profile)`` of one traced run of ``cell``."""
    got = {}
    base_program, base_load, base_context = (run.Program, tracemod.load,
                                             tracemod.Context)

    class Program(base_program):
        def __init__(self, *args, **kwargs):
            super().__init__(*args, **kwargs)
            got["plan"] = self.engine.plan()

        def window(self, seconds, annotate=contextlib.nullcontext):
            got["window"] = super().window(seconds, annotate)
            return got["window"]

    def context(**kwargs):
        got["ctx"] = base_context(**kwargs)
        return got["ctx"]

    run.Program, tracemod.load, tracemod.Context = (Program, scopes.load,
                                                    context)
    try:
        result = run.run(cell, seed, seconds, True, require_tpu=require_tpu,
                         root=root)
    finally:
        run.Program, tracemod.load, tracemod.Context = (
            base_program, base_load, base_context)

    ctx, tr = got["ctx"], got["ctx"].trace
    start, done, _ = got["window"]
    step_ms = [1e3 * (b - a) for a, b in zip([start] + done[:-1], done)]
    metrics = {name: spec.load_reader(name, root).read(ctx)
               for name in SCOPE_READERS}
    listed = {k: v["value"] for k, v in result["metrics"].items()}
    busy_s = tr.busy_per_run("private", ctx.steps)
    share = listed.get("realize.grouped_conv_share")
    per_scope = tr.scopes("private")
    out = {
        "cell": cell.name, "seed": seed,
        "window": {"steps": ctx.steps, "window_s": ctx.window_s,
                   "samples_per_s": ctx.steps * ctx.batch / ctx.window_s,
                   "step_ms_p90": run.quantile(step_ms, 0.9)},
        "scope_metrics": metrics,
        "coverage": tr.coverage("private"),
        "scopes": {k: 1e3 * v / ctx.steps
                   for k, v in sorted(per_scope.items(),
                                      key=lambda kv: -kv[1])},
        "agree": {
            "realize.pe_ms": metrics["realize.pe_ms"],
            "grouped_conv_share_x_busy_ms": (
                share / 100 * busy_s * 1e3 if share and busy_s else None),
            "engine.host_ms": metrics["engine.host_ms"],
            "loop.host_ms": listed.get("loop.host_ms")},
        "engine_spans_ms": {
            name: tr.span_ms(name) for name in (
                "engine.private_step", "engine.noise_key", "engine.dispatch",
                "engine.absorb_clip_aux", "engine.trace")},
        "planner": planner_table(got["plan"], tr, ctx.steps),
        "breakdown": tr.breakdown(),
    }
    return result, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    src = spec.ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        result, out = profile(cell, args.seed, args.seconds)
    except run.NoAccelerator as e:
        run.log(f"not run: {e}")
        return 2
    print(json.dumps(result), flush=True)
    if args.out:
        pathlib.Path(args.out).write_text(json.dumps(out, indent=1) + "\n")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
