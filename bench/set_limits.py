"""Set a cell's limits of ``correct`` from readings at its size.

    python3 bench/set_limits.py --workload <name> --readings <file.jsonl> \
        [--runs <run output> ...]

The readings are the lines ``bench/readings.py`` writes; the numbers that
runs of ``bench/run.py`` compared (the ``checks`` of the last line of each
output given with ``--runs``) count as more readings of the program.  For
each number compared:

- the lower reading is the largest that the program's runs give;
- the upper reading is the smallest of: what the control gives, where that
  is three times the lower or more; what each planted fault gives, where
  that is ten times the lower or more; and 1 for a step that returns its
  state unchanged (the ``update`` number's measure of it), where that is
  three times the lower or more;
- the limit lies between them, at their geometric mean, rounded to two
  significant digits: more room above the lower reading than a fixed
  factor would give where the two lie far apart.

A number with no upper reading gets no limit, and the script says so.
Writes ``bench/limits/<name>.json``.
"""
from __future__ import annotations

import argparse
import json
import math
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from bench import check, spec  # noqa: E402


def limits_from(readings: list) -> dict:
    out = {}
    for name in check.NAMES:
        prog = [r["values"][name] for r in readings if r["kind"] == "program"]
        lower = max(prog)
        uppers = []
        for r in readings:
            v = r["values"][name]
            if r["kind"] == "control" and v >= 3 * lower:
                uppers.append((v, "control " + r["what"]))
            if r["kind"] == "fault" and v >= 10 * lower:
                uppers.append((v, "fault " + r["what"]))
        if name == "update" and 1.0 >= 3 * lower:
            uppers.append((1.0, "fault unchanged_state"))
        entry = {"lower": lower, "program_seeds": len(prog),
                 "program_readings": sorted(prog)}
        if uppers:
            upper, source = min(uppers)
            limit = math.sqrt(lower * upper)
            digits = -int(math.floor(math.log10(limit))) + 1
            entry.update(upper=upper, upper_from=source,
                         limit=round(limit, digits))
        else:
            entry.update(upper=None, limit=None)
        out[name] = entry
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--readings", required=True)
    ap.add_argument("--runs", nargs="*", default=[])
    args = ap.parse_args(argv)
    with open(args.readings) as f:
        readings = [json.loads(line) for line in f if line.strip()]
    readings = [r for r in readings if r["cell"] == args.workload]
    for path in args.runs:
        with open(path) as f:
            lines = [line for line in f if line.strip()]
        if lines:
            checks = json.loads(lines[-1])["checks"]
            readings.append({"kind": "program", "values": {
                k: v["value"] for k, v in checks.items()}})
    table = limits_from(readings)
    missing = [k for k, v in table.items() if v["limit"] is None]
    path = spec.BENCH / "limits" / f"{args.workload}.json"
    with open(path, "w") as f:
        json.dump({"cell": args.workload, "limits": table}, f, indent=1)
        f.write("\n")
    print(json.dumps(table, indent=1))
    if missing:
        print(f"no upper reading for {missing}: no limit holds",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
