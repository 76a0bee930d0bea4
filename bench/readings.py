"""Readings that the limits of ``correct`` are set from, at a cell's size.

    python3 bench/readings.py --workload <name> --seeds 1,2,... \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--out <file.jsonl>]

For each of ``--seeds``: the program's first steps from the seed, as a run's
set-up drives them, compared with the reference (the lower readings).  For
each of ``--control-seeds``: the control, the reference computed in
bfloat16 and put in the program's place (the upper readings).  For each of
``--fault-seeds``: the reference with one fault of the timed path planted,
in the program's place: half of the batch left out, and on several chips
the exchange between them left out.  A step that returns its state
unchanged reads 1 by the check's measure and needs no run.  No window is
measured.  One JSON line per reading goes to ``--out`` and to stdout.
"""
from __future__ import annotations

import argparse
import gc
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from bench import check, run, spec  # noqa: E402


def _seeds(text: str) -> list:
    return [int(s) for s in text.split(",") if s]


def readings(cell: spec.Cell, seeds, control_seeds, fault_seeds, *,
             require_tpu: bool = True, root=spec.ROOT, emit=print):
    import jax
    import jax.numpy as jnp

    devices = run.cell_devices(jax, cell.chips, require_tpu)
    run.use_compile_cache(jax, root)
    ref_mod = spec.load_reference(cell.config, root)
    tr = cell.traffic
    n = tr["checked_steps"]
    faults = ["half_batch"] + (["no_exchange"] if cell.chips > 1 else [])
    for seed in dict.fromkeys(seeds + control_seeds + fault_seeds):
        t = time.perf_counter()
        prog = run.Program(cell, seed, devices, ref_mod)
        got = prog.checked_steps(n)
        ring, init, wkey = prog.ring[:n], prog.init, prog.weights_key
        del prog
        gc.collect()
        t_prog = time.perf_counter() - t
        params0 = init(wkey)
        p0_host = jax.device_get(params0)
        t = time.perf_counter()
        ref = ref_mod.Reference(cell.config, tr, devices).run(
            params0, ring, seed, n)
        t_ref = time.perf_counter() - t
        runs = []
        if seed in seeds:
            runs.append(("program", None, got))
        if seed in control_seeds:
            runs.append(("control", "bfloat16", ref_mod.Reference(
                cell.config, tr, devices, dtype=jnp.bfloat16).run(
                    params0, ring, seed, n)))
        if seed in fault_seeds:
            for fault in faults:
                runs.append(("fault", fault, ref_mod.Reference(
                    cell.config, tr, devices, fault=fault).run(
                        params0, ring, seed, n)))
        for kind, what, out in runs:
            values = check.compare(out, ref, p0_host, tr)
            emit(json.dumps({"cell": cell.name, "kind": kind, "what": what,
                             "seed": seed, "program_s": t_prog,
                             "reference_s": t_ref,
                             "values": {k: v[0] for k, v in values.items()},
                             "at": {k: v[1] for k, v in values.items()}}))
        del params0, ref, runs
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_seeds, default=[])
    ap.add_argument("--control-seeds", type=_seeds, default=[])
    ap.add_argument("--fault-seeds", type=_seeds, default=[])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    src = spec.ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    out = open(args.out, "a") if args.out else None

    def emit(line):
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    try:
        readings(cell, args.seeds, args.control_seeds, args.fault_seeds,
                 emit=emit)
    except run.NoAccelerator as e:
        run.log(f"not run: {e}")
        return 2
    finally:
        if out:
            out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
