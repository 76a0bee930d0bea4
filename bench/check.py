"""The comparison that decides ``correct`` for a DP-SGD training cell.

Set-up drives the program's compiled step from the seed through its first
three steps; the reference follows the same three steps.  Three numbers
are compared, each against a limit of its own (``bench/limits/<cell>.json``):

``loss``
    max over the three steps of |loss_prog - loss_ref| / |loss_ref|.
``grad``
    Step 0's clipped sum as the optimizer got it.  The program's gradient
    is worked out from its AdamW state after one step (m = (1 - b1) g),
    times the batch, less the reference's draw of the step's noise; the
    reference gives its clipped sum.  The noise is taken off both because
    at sigma = 1 it outweighs the clipped sum by hundreds of times, and a
    norm of the sum would not see a wrong clipped sum under it.  A program
    that draws other noise reads the noise's whole norm here.
``update``
    The norm of the parameters' change over the three steps.

``grad`` and ``update`` are taken leaf by leaf and reported for the worst
leaf: the gap between the program's norm and the reference's, over the
larger of the reference's norm of that leaf and of the median leaf.
``update`` leaves out leaves whose reference gradient (noise included) is
under a thousandth of the median leaf's, which move by round-off alone.
"""
from __future__ import annotations

import jax
import numpy as np

NAMES = ("loss", "grad", "update")


def _flat(tree) -> dict:
    return {jax.tree_util.keystr(kp): np.asarray(leaf)
            for kp, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _norm(a) -> float:
    return float(np.sqrt(np.sum(np.square(a, dtype=np.float64))))


def worst_gap(prog: dict, ref: dict, keep=None) -> tuple[float, str]:
    """(gap, leaf) of the worst leaf: |prog - ref| / max(ref, median ref),
    over per-leaf norms keyed alike."""
    keys = [k for k in ref if keep is None or k in keep]
    med = float(np.median([ref[k] for k in keys]))
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
            for k in keys}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def compare(prog: dict, ref: dict, params0, traffic: dict) -> dict:
    """The numbers compared, as {name: (value, worst leaf or step)}.

    ``prog``: {"losses", "m1" (AdamW first moment after step 0),
    "params" (after the checked steps)}; ``ref``: what
    ``Reference.run`` returns; ``params0``: the starting parameters."""
    lp, lr = np.asarray(prog["losses"], np.float64), \
        np.asarray(ref["losses"], np.float64)
    rel = np.abs(lp - lr) / np.maximum(np.abs(lr), 1e-30)
    out = {"loss": (float(np.max(rel)), f"step {int(np.argmax(rel))}")}

    b1 = traffic["optimizer"]["b1"]
    batch = traffic["batch"]
    m1, s0, n0 = _flat(prog["m1"]), _flat(ref["S0"]), _flat(ref["N0"])
    sum_prog = {k: _norm(batch * (m1[k] / np.float32(1 - b1)) - n0[k])
                for k in s0}
    sum_ref = {k: _norm(s0[k]) for k in s0}
    out["grad"] = worst_gap(sum_prog, sum_ref)

    p0, pp, pr = _flat(params0), _flat(prog["params"]), _flat(ref["params"])
    g_ref = {k: _norm((s0[k] + n0[k]) / batch) for k in s0}
    med = float(np.median(list(g_ref.values())))
    keep = {k for k, v in g_ref.items() if v >= 1e-3 * med}
    out["update"] = worst_gap({k: _norm(pp[k] - p0[k]) for k in p0},
                              {k: _norm(pr[k] - p0[k]) for k in p0}, keep)
    return out


def judge(values: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit", "at"}}): correct when every
    number is finite and within its limit."""
    checks = {}
    ok = True
    for name in NAMES:
        value, at = values[name]
        limit = float(limits[name])
        good = bool(np.isfinite(value)) and value <= limit
        ok = ok and good
        checks[name] = {"value": value, "limit": limit, "at": at}
    return ok, checks
