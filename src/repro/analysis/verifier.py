"""Trace a PrivacyEngine's private step and verify DP invariants.

:func:`verify_engine` is what ``engine.verify()`` and the ``dpcheck``
CLI call: it traces the engine's *unjitted* step closure to a
ClosedJaxpr with ``jax.make_jaxpr`` (no execution, no devices needed —
the mesh lane verifies the declared shardings, not a compiled
executable), flattens it (:mod:`repro.analysis.graph`), and runs four
passes:

  * taint      (:mod:`repro.analysis.taint`)      — clip before any
    batch reduction on every path to the released params/opt state;
  * noise      (:mod:`repro.analysis.noise`)      — one fresh f32
    Gaussian per released leaf at scale sigma·C, keys from the step key
    input, no reuse;
  * sharding   (:mod:`repro.analysis.shardcheck`) — mesh lanes: batch
    data-sharded, everything else (incl. the key and every output)
    replicated, clip decisions global, noise aggregate-level;
  * plan       (:mod:`repro.analysis.plancheck`)  — the ExecPlan's
    declared realizations actually executed (marker + STATS census),
    live fingerprint, collective-traffic warning.

Violations that only feed the *monitoring* outputs (the mean loss, clip
fractions) are filtered by a backward slice from the params/optimizer
outputs — ``mean(losses)`` legitimately averages over examples; what it
feeds is released as a float, not as the model update, and is outside
the clip→noise mechanism this verifier polices.
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp

from repro.analysis import graph as graphlib
from repro.analysis import noise as noiselib
from repro.analysis import plancheck, shardcheck
from repro.analysis import taint as taintlib
from repro.analysis.graph import Var
from repro.analysis.report import Finding, VerifyReport


def _spec(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), tree)


def _opt_spec(engine, opt):
    if opt is not None:
        return _spec(opt)
    name = getattr(engine, "_optimizer_name", None)
    from repro.optim import adamw_init, sgdm_init
    table = {"adamw": adamw_init, "sgdm": sgdm_init}
    if name not in table:
        raise ValueError(
            "engine uses a custom optimizer callable; pass opt= (a live "
            "or abstract optimizer state) to verify()")
    return jax.eval_shape(table[name], engine._params_spec)


def _clip_state_spec(engine, B):
    clip = engine.dp.clipping
    if clip.mode == "stale":
        # Verify the steady state (the bootstrap step IS the flat
        # pipeline, covered by the flat lane).
        return {"prev_norms_sq": jax.ShapeDtypeStruct((B,), jnp.float32)}
    if clip.mode == "per_layer" and clip.budgets == "auto":
        return _spec(engine._clip_state())
    return {}


def _classify_outputs(graph, out_shape):
    """Vars feeding the released params/opt outputs (tuple slots 0, 1)."""
    leaves = jax.tree_util.tree_leaves_with_path(out_shape)
    sinks = []
    for (kp, _), v in zip(leaves, graph.outvars):
        slot = getattr(kp[0], "idx", None)
        if slot in (0, 1) and isinstance(v, Var):
            sinks.append(v)
    return sinks


def verify_engine(engine, *, opt=None,
                  coll_bytes_warn: Optional[float] = None) -> VerifyReport:
    """Statically verify one engine's private step.  Returns a
    :class:`~repro.analysis.report.VerifyReport`; never executes the
    step."""
    from repro.core import costmodel
    from repro.core.tapper import STATS

    findings: List[Finding] = []
    checked = {}
    mode = engine.dp.clipping.mode
    sigma_mult = engine.dp.noise_multiplier
    l2_clip = engine.dp.l2_clip
    B = jax.tree.leaves(engine._batch_spec)[0].shape[0]
    stale_steady = mode == "stale"

    # Planning (and any probes) happen before the STATS snapshot, so the
    # traced-step census below sees only the step's own phases.
    plan = engine._exec_plan()
    m = engine.microbatches()
    step = engine._step_fn()

    params_spec = engine._params_spec
    opt_spec = _opt_spec(engine, opt)
    key_spec = jax.ShapeDtypeStruct((2,), jnp.uint32)
    cs_spec = _clip_state_spec(engine, B)

    before = {k: getattr(STATS, k)
              for k in ("forwards", "backwards", "probes", "fused")}
    closed, out_shape = jax.make_jaxpr(step, return_shape=True)(
        params_spec, opt_spec, engine._batch_spec, key_spec, cs_spec)
    stats_delta = {k: getattr(STATS, k) - v for k, v in before.items()}

    graph = graphlib.flatten(closed)

    # -- input var bookkeeping --------------------------------------------
    n_p = len(jax.tree.leaves(params_spec))
    n_o = len(jax.tree.leaves(opt_spec))
    batch_leaves = jax.tree.leaves(engine._batch_spec)
    n_b = len(batch_leaves)
    invars = graph.invars
    batch_vars = invars[n_p + n_o:n_p + n_o + n_b]
    key_vars = set(invars[n_p + n_o + n_b:n_p + n_o + n_b + 1])
    cs_vars = invars[n_p + n_o + n_b + 1:]

    init = {}
    for v, leaf in zip(batch_vars, batch_leaves):
        if leaf.shape and leaf.shape[0] == B:
            init[v] = taintlib.Taint(frozenset({0}))
    for v, (path, leaf) in zip(
            cs_vars, sorted(
                ((k, l) for k, l in (cs_spec or {}).items()))):
        if path == "prev_norms_sq":
            init[v] = taintlib.Taint(frozenset({0}))

    # -- taint pass --------------------------------------------------------
    res = taintlib.TaintPass(graph, B).run(init)
    sinks = _classify_outputs(graph, out_shape)
    released = graph.backward_slice(sinks)
    top_ids = {id(n) for n in graph.nodes}
    for viol in res.violations:
        if id(viol.node) in top_ids and not any(
                isinstance(ov, Var) and ov in released
                for ov in viol.node.outvars):
            continue  # feeds only the loss/monitoring outputs
        findings.append(Finding(
            "error", "unclipped_batch_reduction",
            viol.message + " on a path to the released model update",
            "taint"))
    if res.approx:
        uniq = sorted(set(res.approx))
        findings.append(Finding(
            "info", "taint_approximation",
            f"unmodeled primitives handled conservatively: {uniq[:8]}",
            "taint"))
    checked["taint"] = (
        f"all batch-axis reductions reaching the released update cross a "
        f"clip contraction ({len(graph.nodes)} top-level eqns, B={B})")

    # -- clip marker discipline -------------------------------------------
    clip_markers = [n for n, _ in graph.markers()
                    if n.params.get("kind") == "clip_coef"]
    if not clip_markers:
        findings.append(Finding(
            "error", "clip_missing",
            "no clip-coefficient marker in the traced step — the "
            "per-example clip was removed or replaced", "taint"))
    else:
        modes = {n.params.get("mode") for n in clip_markers}
        if mode not in modes and not (mode == "stale" and "flat" in modes):
            findings.append(Finding(
                "error", "clip_mode_mismatch",
                f"engine clips {mode!r} but the traced coefficients are "
                f"{sorted(modes)}", "taint"))
        for n in clip_markers:
            c = n.params.get("l2_clip")
            if c is not None and abs(float(c) - l2_clip) > 1e-9 * max(
                    l2_clip, 1.0):
                findings.append(Finding(
                    "error", "clip_bound_mismatch",
                    f"traced clip bound {c} != configured C={l2_clip}",
                    "taint"))
                break
    checked["clip"] = (f"{len(clip_markers)} clip-coefficient site(s), "
                       f"mode {mode!r}, C={l2_clip}")

    # -- noise pass --------------------------------------------------------
    findings.extend(noiselib.check_noise(
        graph, key_inputs=key_vars, n_param_leaves=n_p,
        noise_multiplier=sigma_mult, l2_clip=l2_clip))
    checked["noise"] = (
        f"one f32 Gaussian per released leaf ({n_p} leaves) at "
        f"sigma·C = {sigma_mult * l2_clip:g}, keys chained to the step "
        f"key input (fold_in(run_key, step) enforced host-side)"
        if sigma_mult > 0 else "noise_multiplier == 0: no draws expected")

    # -- sharding pass -----------------------------------------------------
    mesh_axes = engine._mesh_axes
    shardings = engine._step_shardings()
    findings.extend(shardcheck.check_sharding(
        graph, taints=res.taints, batch_size=B, mesh_axes=mesh_axes,
        data_size=costmodel.mesh_data_size(mesh_axes),
        in_shardings=shardings[0] if shardings else None,
        out_shardings=shardings[1] if shardings else None))
    model_axes = costmodel.mesh_model_axes(mesh_axes)
    params_partitioned = bool(shardings) and any(
        not shardcheck._is_replicated(s)
        for s in jax.tree.leaves(shardings[0][0]))
    layout = ("params/opt partitioned over "
              + "x".join(a for a, _ in model_axes)
              + ", key replicated, outputs data-replicated"
              if model_axes and params_partitioned
              else "params/opt/key/outputs replicated")
    checked["sharding"] = (
        f"batch data-sharded, {layout} on "
        f"{costmodel.format_mesh(mesh_axes)}; clip decisions global, "
        f"noise drawn once" if mesh_axes
        else "no mesh: single-device step")

    # -- plan pass ---------------------------------------------------------
    expected_fp = (engine._fingerprint()
                   if plan is not None and m == 1 else None)
    kw = {} if coll_bytes_warn is None else {
        "coll_bytes_warn": coll_bytes_warn}
    findings.extend(plancheck.check_plan(
        graph, plan=plan, clip_mode=mode, stale_steady=stale_steady,
        stats_delta=stats_delta, expected_fingerprint=expected_fp, **kw))
    checked["plan"] = (
        f"{len(plan.groups)} group realizations present in the graph, "
        f"STATS census {stats_delta}, fingerprint {plan.fingerprint or '-'}"
        if plan is not None
        else f"materializing strategy {engine.dp.strategy!r}: no plan to "
        f"check")

    owner = getattr(engine.apply_fn, "__self__", None)
    model = (type(owner).__qualname__ if owner is not None
             else getattr(engine.apply_fn, "__qualname__", "<fn>"))
    target = (f"{model} "
              f"clip={mode} sigma={sigma_mult} B={B} "
              f"mesh={costmodel.format_mesh(mesh_axes)}"
              + (f" microbatches={m}" if m != 1 else ""))
    order = {"error": 0, "warning": 1, "info": 2}
    findings.sort(key=lambda f: order[f.severity])
    return VerifyReport(target=target, findings=findings, checked=checked)
