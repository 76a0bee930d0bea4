"""Per-example gradient strategies.

The paper's three strategies materialize every per-example gradient; the
tests use them as oracles:

  * ``naive`` — batch-size-1 loop (``lax.map``); the semantics oracle.
  * ``multi`` — ``vmap(grad)``: JAX's native realization of "B model copies
    sharing parameters" (§2 of the paper, Goodfellow's GitHub suggestion).
  * ``crb``   — the paper's chain-rule-based method: one standard backward
    (via output taps), then per-layer reconstruction of per-example grads
    from (captured input, output cotangent) — outer products for dense
    layers, the grouped-convolution trick (Algorithms 1–2) for convs.

The other three execute a per-layer :class:`~repro.core.costmodel.ExecPlan`
through one executor, :func:`planned_clipped_sum`: one capture backward,
each parameter group's norm, the clip coefficients, then each group's
share of the clipped sum.

  * ``auto``  — the planner chooses, for every tapped layer, the cheapest
    exact norm realization (Gram ghost-norm — dense or im2col'd conv —
    streamed materialization, rank-1, segsum) and the sum phase (reuse
    grads the norm already materialized, book-keeping contraction, or a
    shared weighted backward when contractions would cost more than one
    extra backward).  The plan is keyed on (model, batch/param shapes),
    so steady-state training runs one forward and one backward per step
    unless the plan takes the weighted backward.
  * ``bk``    — "book-keeping": no stash; every group's sum is a weighted
    contraction from the captures already in hand.
  * ``ghost`` — no stash; every group's sum comes from one second,
    weighted backward pass (two forwards and two backwards per step).
    Flat clipping only.

``apply_fn(params, batch, tapper) -> (B,) per-example losses`` is the only
contract a model must satisfy.  Execution counts (forwards / backwards /
probes) are tracked in :data:`repro.core.tapper.STATS`.

Sharded execution is the same code: strategies stay global-view pure
``jnp``, and the engine's declared in/out shardings (batch over the
data axes; params over ``model`` when tensor-sharded) make GSPMD insert
the collectives — per-example norm partials psum over ``model``, the
(B,)-scalar norms all-reduce over the data axes exactly once per layer
group, and the clipped+noised update all-reduces back to
data-replicated.  Nothing in this module branches on the mesh; the
planner (:mod:`repro.core.costmodel`) prices each of those collectives
on the axis it actually crosses.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.analysis.markers import tag
from repro.core import costmodel, kinds
from repro.core.tapper import (STATS, Tapper, capture_backward, get_subtree,
                               probe, set_subtree)

MATERIALIZING = ("naive", "multi", "crb")
STRATEGIES = MATERIALIZING + costmodel.PLANNED_STRATEGIES


# ---------------------------------------------------------------------------
# naive & multi


def _single_example_grad_fn(apply_fn, params):
    def gb(ex):
        ex1 = jax.tree.map(lambda a: a[None], ex)

        def loss(p):
            return apply_fn(p, ex1, Tapper())[0]

        return jax.value_and_grad(loss)(params)

    return gb


def naive_per_example_grads(apply_fn, params, batch):
    """Batch-size-1 loop — sequential, the paper's `naive`."""
    losses, grads = lax.map(_single_example_grad_fn(apply_fn, params), batch)
    return losses, grads


def multi_per_example_grads(apply_fn, params, batch):
    """vmap(grad) — the paper's `multi` (model copies sharing params)."""
    losses, grads = jax.vmap(_single_example_grad_fn(apply_fn, params))(batch)
    return losses, grads


# ---------------------------------------------------------------------------
# crb: capture + reconstruct


def _capture(apply_fn, params, batch):
    make_taps, metas, _ = probe(apply_fn, params, batch)
    losses, caps, dtaps = capture_backward(apply_fn, params, batch, make_taps())
    return losses, caps, dtaps, metas


def _accumulate_param_grads(acc: dict, path: tuple, sub: dict):
    """acc[path][key] += sub[key] (creating entries)."""
    slot = acc.setdefault(path, {})
    for k, v in sub.items():
        slot[k] = slot[k] + v if k in slot else v


def _grads_to_tree(acc: dict) -> dict:
    tree: dict = {}
    for path, sub in acc.items():
        for k, v in sub.items():
            tree = set_subtree(tree, path + (k,), v)
    return tree


def check_coverage(params, grads_tree) -> list[str]:
    """Param leaves with no per-example gradient contribution."""
    p_paths = {jax.tree_util.keystr(kp)
               for kp, _ in jax.tree_util.tree_leaves_with_path(params)}
    g_paths = {jax.tree_util.keystr(kp)
               for kp, _ in jax.tree_util.tree_leaves_with_path(grads_tree)}
    return sorted(p_paths - g_paths)


def crb_per_example_grads(apply_fn, params, batch, *, conv_impl: str = "auto",
                          check: bool = True):
    """The paper's method: 1 backward + per-layer reconstruction."""
    losses, caps, dtaps, metas = _capture(apply_fn, params, batch)
    acc: dict = {}
    for name, meta in metas.items():
        pe = kinds.apply_kind(
            "pe_grad", meta, caps[name], dtaps[name],
            params_sub=get_subtree(params, meta.path), conv_impl=conv_impl)
        _accumulate_param_grads(acc, meta.path, pe)
    grads = _grads_to_tree(acc)
    if check:
        missing = check_coverage(params, grads)
        if missing:
            raise ValueError(f"params without per-example grads: {missing}")
    return losses, grads


# ---------------------------------------------------------------------------
# scopes of the private step's phases


def group_key_of(path: tuple) -> str:
    """The clip-budget key of a parameter group: its "/"-joined path."""
    return "/".join(str(p) for p in path)


def phase_scope(phase: str, method: str | None = None,
                path: tuple | None = None):
    """``jax.named_scope`` of one phase of the private step: ``dp.<phase>``
    or, for one parameter group, ``dp.<phase>/<method>/<group>`` with the
    group key's "/" made "." (one component).  The device operations the
    phase compiles into carry the name in their metadata, and the profiler
    trace keeps it; ``method`` and the group are the strings the phase's
    ``dp_tag`` carries."""
    name = f"dp.{phase}"
    if method is not None:
        name += f"/{method}"
    if path is not None:
        name += "/" + group_key_of(path).replace("/", ".")
    return jax.named_scope(name)


# ---------------------------------------------------------------------------
# clipped gradient sums (the DP-SGD core)


def clip_coefficients(norms_sq, l2_clip, eps: float = 1e-12, *,
                      mode: str = "flat"):
    with phase_scope("clip"):
        norms = jnp.sqrt(norms_sq + eps)
        coef = jnp.minimum(1.0, l2_clip / norms)
    # Structural marker the static verifier keys on: downstream of this
    # tag, multiplying by ``coef`` IS the clip contraction.  A mutant
    # that replaces the coefficients wholesale loses the tag — itself a
    # finding.  ``mode`` records which policy produced them ("stale"
    # when fed lagged norms).
    params = {"kind": "clip_coef", "mode": mode}
    try:
        params["l2_clip"] = float(l2_clip)
    except TypeError:  # traced bound: still tag, just without the value
        pass
    return tag(coef, **params)


def per_layer_clip_coefficients(group_norms_sq, budgets, eps: float = 1e-12):
    """(G, B) coefficients: each group clipped against its own budget."""
    with phase_scope("clip"):
        norms = jnp.sqrt(group_norms_sq + eps)
        coef = jnp.minimum(1.0, budgets[:, None] / norms)
    return tag(coef, kind="clip_coef", mode="per_layer")


def _pe_tree_norms_sq(pe_grads):
    return kinds._sumsq(pe_grads)


def _flat_detail(coef):
    return {"group_keys": (), "group_norms_sq": None, "coef": coef,
            "budgets": None}


def clipped_grad_sum(apply_fn, params, batch, **kw):
    """Returns (per-example losses, Σ_b clip(g_b), per-example norms²) —
    see :func:`clipped_grad_sum_detailed` for the keyword surface; this
    wrapper drops the detail dict."""
    losses, gsum, norms_sq, _ = clipped_grad_sum_detailed(
        apply_fn, params, batch, **kw)
    return losses, gsum, norms_sq


def clipped_grad_sum_detailed(apply_fn, params, batch, *, l2_clip: float,
                              strategy: str = "ghost",
                              norm_method: str = "auto",
                              conv_impl: str = "auto", check: bool = False,
                              embed_method: str = "segsum",
                              conv_norm: str = "auto", overrides=None,
                              mem_budget: int | None = None, plan=None,
                              clip_policy=None, budgets=None,
                              prev_norms_sq=None):
    """Returns (per-example losses, Σ_b clip(g_b), per-example norms²,
    detail).

    ``naive``/``multi``/``crb`` materialize the per-example gradients and
    clip flat.  ``ghost``/``bk``/``auto`` execute the plan
    :func:`~repro.core.costmodel.get_plan` builds for these knobs (or
    ``plan``, a pre-built, possibly deserialized ExecPlan) with
    :func:`planned_clipped_sum`.  ``conv_norm`` (auto | ghost | pe |
    pallas) picks the conv norm realization; ``overrides`` pins
    individual layers by tap-name glob.

    ``clip_policy`` (a :class:`~repro.core.clipping.ClipPolicy`; None =
    flat) selects the clipping mode of a planned strategy
    (:class:`~repro.core.clipping.DPConfig` rejects a non-flat mode with
    any strategy but ``auto`` and ``bk``).  ``budgets`` injects a
    resolved (G,) per-layer budget array (else the policy's static split
    is resolved against the sorted group keys); ``prev_norms_sq`` feeds
    stale mode's lagged (B,) norms.

    ``detail``: ``group_keys`` (static tuple), ``group_norms_sq`` ((G, B)
    under per_layer, else None), ``coef`` (the applied coefficients —
    (B,) flat/stale, (G, B) per_layer), ``budgets`` ((G,) under
    per_layer, else None).
    """
    if strategy in MATERIALIZING:
        if strategy == "naive":
            losses, pe = naive_per_example_grads(apply_fn, params, batch)
        elif strategy == "multi":
            losses, pe = multi_per_example_grads(apply_fn, params, batch)
        else:
            losses, pe = crb_per_example_grads(
                apply_fn, params, batch, conv_impl=conv_impl, check=check)
        norms_sq = _pe_tree_norms_sq(pe)
        coef = clip_coefficients(norms_sq, l2_clip)
        gsum = jax.tree.map(
            lambda g: jnp.einsum("b...,b->...", g.astype(jnp.float32), coef),
            pe)
        return losses, gsum, norms_sq, _flat_detail(coef)
    mode = clip_policy.mode if clip_policy is not None else "flat"
    if mode == "stale" and prev_norms_sq is None:
        raise ValueError(
            "stale clipping needs prev_norms_sq (the engine bootstraps "
            "the first step with flat clipping and threads the state)")
    if plan is None:
        plan = costmodel.get_plan(
            apply_fn, params, batch, norm_method=norm_method,
            embed_method=embed_method, conv_norm=conv_norm,
            conv_impl=conv_impl,
            mem_budget=mem_budget or costmodel.STREAM_MEM_BUDGET,
            overrides=overrides, clip_mode=mode,
            clip_fused=(clip_policy.fused if clip_policy is not None
                        else True), strategy=strategy)
    return planned_clipped_sum(apply_fn, params, batch, plan,
                               l2_clip=l2_clip, conv_impl=conv_impl,
                               check=check, clip_policy=clip_policy,
                               budgets=budgets,
                               prev_norms_sq=prev_norms_sq)


def ghost_norms(apply_fn, params, batch, **norm_kw):
    """Per-example squared norms of the full gradient, with no clipped sum:
    the norm phase of the ``ghost`` plan for these knobs (those of
    :func:`~repro.core.costmodel.get_plan`; ``attn_norm`` pins every
    attention block's realization).  Returns (losses, (B,) norms²,
    (captures, tap cotangents, layer metas))."""
    attn_norm = norm_kw.pop("attn_norm", "auto")
    plan = costmodel.get_plan(apply_fn, params, batch, strategy="ghost",
                              **norm_kw)
    if attn_norm != "auto":
        pins = tuple((n, attn_norm) for n, lp in plan.layers.items()
                     if lp.kind == "attn")
        norm_kw["overrides"] = pins + costmodel.normalize_overrides(
            norm_kw.get("overrides"))
        plan = costmodel.get_plan(apply_fn, params, batch, strategy="ghost",
                                  **norm_kw)
    losses, caps, dtaps, metas = capture_backward(
        apply_fn, params, batch, plan.make_taps(), with_metas=True)
    conv_impl = norm_kw.get("conv_impl", "auto")
    norms_sq = sum(_planned_group_norm(g, plan, metas, caps, dtaps, params,
                                       conv_impl, {})
                   for g in plan.groups)
    return losses, norms_sq, (caps, dtaps, metas)


# ---------------------------------------------------------------------------
# The plan executor: strategies ghost, bk and auto


def _norm_kwargs(lp):
    if lp.kind in ("dense", "seg_dense"):
        return {"norm_method": lp.norm_method}
    if lp.kind == "embed":
        return {"embed_method": lp.norm_method}
    if lp.kind == "conv":
        return {"conv_norm": lp.norm_method}
    if lp.kind == "attn":
        return {"attn_norm": lp.norm_method}
    return {}


def _group_norm_tag(n_sq, g, method: str, fused: bool = False):
    """Mark one plan group's realized (B,) squared norms for the static
    verifier (kind=group_norm): which group, which realized method, and
    whether a fused single-pass produced them."""
    return tag(n_sq, kind="group_norm", group=group_key_of(g.path),
               method=method, fused=fused)


def _norm_method_of(g, plan) -> str:
    """The realization of one plan group's norm, as its ``dp.norm`` scope
    names it: the planned norm method of a single layer (also where its
    per-example grads are stashed), ``tied`` or ``pe``."""
    if g.norm_mode == "single":
        return plan.layers[g.members[0]].norm_method
    return "tied" if g.norm_mode == "tied" else "pe"


def _planned_group_norm(g, plan, metas, caps, dtaps, params, conv_impl,
                        stash):
    """Phase-1 norm of one plan group: (B,) squared norms, stashing any
    per-example grads the chosen realization materialized."""
    with phase_scope("norm", _norm_method_of(g, plan), g.path):
        return _realize_group_norm(g, plan, metas, caps, dtaps, params,
                                   conv_impl, stash)


def _realize_group_norm(g, plan, metas, caps, dtaps, params, conv_impl,
                        stash):
    psub = get_subtree(params, g.path)
    if g.norm_mode == "single":
        n = g.members[0]
        lp, meta = plan.layers[n], metas[n]
        if lp.stash:
            pe = kinds.apply_kind("pe_grad", meta, caps[n], dtaps[n],
                                  params_sub=psub, conv_impl=conv_impl)
            stash[n] = pe
            return _group_norm_tag(kinds._sumsq(pe), g, "stash")
        return _group_norm_tag(kinds.apply_kind(
            "norm_sq", meta, caps[n], dtaps[n], params_sub=psub,
            conv_impl=conv_impl, **_norm_kwargs(lp)), g, lp.norm_method)
    if g.norm_mode == "tied":
        n_e = next(n for n in g.members if metas[n].kind == "embed")
        n_d = next(n for n in g.members if metas[n].kind == "dense")
        n_g = kinds.apply_kind(
            "norm_sq", metas[n_e], caps[n_e], dtaps[n_e],
            params_sub=psub, **_norm_kwargs(plan.layers[n_e]))
        n_g = n_g + kinds.apply_kind(
            "norm_sq", metas[n_d], caps[n_d], dtaps[n_d],
            params_sub=psub, **_norm_kwargs(plan.layers[n_d]))
        return _group_norm_tag(n_g + kinds.tied_embed_head_cross(
            caps[n_e], dtaps[n_e], caps[n_d], dtaps[n_d]), g, "tied")
    # group_pe: exact generic fallback, materialized once
    pe_sum: dict = {}
    for n in g.members:
        pe = kinds.apply_kind("pe_grad", metas[n], caps[n], dtaps[n],
                              params_sub=psub, conv_impl=conv_impl)
        for k, v in pe.items():
            pe_sum[k] = pe_sum[k] + v if k in pe_sum else v
    if g.sum_method == "stash":
        stash[g.path] = pe_sum
    return _group_norm_tag(kinds._sumsq(pe_sum), g, "pe")


def _weighted_stash_sum(pe, w):
    return jax.tree.map(
        lambda leaf: jnp.einsum("b...,b->...", leaf.astype(jnp.float32), w),
        pe)


def _stale_group_norm_contrib(g, plan, metas, caps, dtaps, params, coef,
                              conv_impl, fused_ok, acc):
    """Stale-coefficient single pass over one plan group: the norm (for
    the *next* step's coefficients) and the weighted contribution come
    from the same captures, with the fused ``gram_norm_fused``
    realization where the plan selected it."""
    psub = get_subtree(params, g.path)
    norm_scope = phase_scope("norm", _norm_method_of(g, plan), g.path)
    if g.norm_mode == "single":
        n = g.members[0]
        lp, meta = plan.layers[n], metas[n]
        if lp.fused and fused_ok:
            # One pass gives both: the norm's scope holds it.
            with norm_scope:
                n_g, contrib = kinds.apply_norm_contrib(
                    meta, caps[n], dtaps[n], weights=coef, params_sub=psub,
                    fused=True, conv_impl=conv_impl, **_norm_kwargs(lp))
                _accumulate_param_grads(acc, g.path, contrib)
            return _group_norm_tag(n_g, g, lp.norm_method, fused=True)
        if lp.stash:
            with norm_scope:
                pe = kinds.apply_kind("pe_grad", meta, caps[n], dtaps[n],
                                      params_sub=psub, conv_impl=conv_impl)
                n_g = kinds._sumsq(pe)
            with phase_scope("contrib", "stash", g.path):
                _accumulate_param_grads(acc, g.path,
                                        _weighted_stash_sum(pe, coef))
            return _group_norm_tag(n_g, g, "stash")
        with norm_scope:
            n_g = kinds.apply_kind(
                "norm_sq", meta, caps[n], dtaps[n], params_sub=psub,
                conv_impl=conv_impl, **_norm_kwargs(lp))
        with phase_scope("contrib", "contrib", g.path):
            _accumulate_param_grads(acc, g.path, kinds.apply_kind(
                "contrib", meta, caps[n], dtaps[n], params_sub=psub,
                weights=coef, conv_impl=conv_impl))
        return _group_norm_tag(n_g, g, lp.norm_method)
    if g.norm_mode == "tied":
        stash: dict = {}
        n_g = _planned_group_norm(g, plan, metas, caps, dtaps, params,
                                  conv_impl, stash)
        with phase_scope("contrib", "contrib", g.path):
            for n in g.members:
                _accumulate_param_grads(acc, g.path, kinds.apply_kind(
                    "contrib", metas[n], caps[n], dtaps[n], params_sub=psub,
                    weights=coef, conv_impl=conv_impl))
        return n_g
    # group_pe: the materialized summed per-example grad serves both.
    with norm_scope:
        pe_sum: dict = {}
        for n in g.members:
            pe = kinds.apply_kind("pe_grad", metas[n], caps[n], dtaps[n],
                                  params_sub=psub, conv_impl=conv_impl)
            for k, v in pe.items():
                pe_sum[k] = pe_sum[k] + v if k in pe_sum else v
        n_g = kinds._sumsq(pe_sum)
    with phase_scope("contrib", "stash", g.path):
        _accumulate_param_grads(acc, g.path,
                                _weighted_stash_sum(pe_sum, coef))
    return _group_norm_tag(n_g, g, "pe")


def planned_clipped_sum(apply_fn, params, batch, plan, *, l2_clip: float,
                        conv_impl: str = "auto", check: bool = False,
                        clip_policy=None, budgets=None, prev_norms_sq=None):
    """Execute a :class:`~repro.core.costmodel.ExecPlan`: one capture
    backward, per-layer planned norms (stashing any per-example grads the
    norm phase materialized), then the clipped sum from stashes /
    book-keeping contractions / at most one shared weighted backward.

    Returns (losses, gsum, total norms², detail) — see
    :func:`clipped_grad_sum_detailed` for the detail contract.

    The clipping mode generalizes the coefficient flow: ``flat`` applies
    one (B,) coefficient vector everywhere; ``per_layer`` gives each
    parameter group its own (B,) coefficients from its own norms and
    budget (so the shared weighted backward, which can only realize one
    weight per example, is never planned); ``stale`` knows every
    coefficient *entering* the pass and collapses norm + sum into one
    sweep over the captures, fused (``gram_norm_fused``) where the plan
    marked it.  The plan must have been built for the executing mode —
    a mismatch fails loudly, like any other stale-plan field.

    Layer metadata comes from the capture trace itself (the *live* metas),
    not the plan: a deserialized plan cannot carry ``local_vjp`` closures,
    and validating the name sets against each other makes a stale plan fail
    loudly instead of silently misassigning decisions."""
    mode = clip_policy.mode if clip_policy is not None else "flat"
    fused_ok = clip_policy.fused if clip_policy is not None else True
    costmodel.check_plan_matches(plan, clip_mode=mode)
    losses, caps, dtaps, metas = capture_backward(
        apply_fn, params, batch, plan.make_taps(), with_metas=True)
    if set(metas) != set(plan.layers):
        missing = sorted(set(plan.layers) - set(metas))
        extra = sorted(set(metas) - set(plan.layers))
        raise ValueError(
            f"ExecPlan {plan.fingerprint or '<unfingerprinted>'} "
            f"(mesh {costmodel.format_mesh(tuple(plan.mesh))}) does not "
            f"match this model: plan-only layers {missing}, model-only "
            f"layers {extra} — re-plan (stale or mismatched serialized "
            f"plan?)")
    group_keys = tuple(group_key_of(g.path) for g in plan.groups)
    if mode != "flat":
        bad = [group_keys[i] for i, g in enumerate(plan.groups)
               if g.sum_method == "backward"]
        if bad:
            raise ValueError(
                f"plan uses the shared weighted backward for {bad} — "
                f"incompatible with clipping mode {mode!r} (re-plan)")

    if mode == "stale":
        if prev_norms_sq is None:
            raise ValueError("stale clipping needs prev_norms_sq")
        coef = lax.stop_gradient(
            clip_coefficients(prev_norms_sq, l2_clip, mode="stale"))
        acc: dict = {}
        total = 0.0
        for g in plan.groups:
            total = total + _stale_group_norm_contrib(
                g, plan, metas, caps, dtaps, params, coef, conv_impl,
                fused_ok, acc)
        return losses, _plan_sum(params, acc, check), total, \
            _flat_detail(coef)

    stash: dict = {}
    group_ns = jnp.stack([
        _planned_group_norm(g, plan, metas, caps, dtaps, params, conv_impl,
                            stash)
        for g in plan.groups])                                   # (G, B)
    total = jnp.sum(group_ns, axis=0)

    if mode == "per_layer":
        if budgets is None:
            from repro.core.clipping import resolve_budgets
            budgets = resolve_budgets(clip_policy, l2_clip, group_keys)
        coef = lax.stop_gradient(
            per_layer_clip_coefficients(group_ns, budgets))      # (G, B)
        detail = {"group_keys": group_keys, "group_norms_sq": group_ns,
                  "coef": coef, "budgets": budgets}
        weights = list(coef)
    else:
        flat_coef = lax.stop_gradient(clip_coefficients(total, l2_clip))
        detail = _flat_detail(flat_coef)
        weights = [flat_coef] * len(plan.groups)

    wgrads = None
    if plan.needs_backward:
        def wloss(p):
            losses2 = apply_fn(p, batch, Tapper())
            return jnp.sum(losses2 * detail["coef"])

        STATS.forwards += 1
        STATS.backwards += 1
        with phase_scope("contrib", "backward"):
            wgrads = jax.grad(wloss)(params)

    acc: dict = {}
    for gi, g in enumerate(plan.groups):
        w = weights[gi]
        if g.sum_method == "backward":
            _accumulate_param_grads(acc, g.path, get_subtree(wgrads, g.path))
            continue
        with phase_scope("contrib", g.sum_method, g.path):
            if g.sum_method == "stash":
                pe = stash[g.members[0] if g.norm_mode == "single"
                           else g.path]
                _accumulate_param_grads(acc, g.path,
                                        _weighted_stash_sum(pe, w))
                continue
            psub = get_subtree(params, g.path)
            for n in g.members:
                contrib = kinds.apply_kind(
                    "contrib", metas[n], caps[n], dtaps[n], params_sub=psub,
                    weights=w, conv_impl=conv_impl)
                _accumulate_param_grads(acc, g.path, contrib)

    return losses, _plan_sum(params, acc, check), total, detail


def _plan_sum(params, acc, check):
    gsum = _grads_to_tree(acc)
    if check:
        missing = check_coverage(params, gsum)
        if missing:
            raise ValueError(f"plan missing param contribs: {missing}")
    return gsum


def per_example_grads(apply_fn, params, batch, strategy: str = "crb", **kw):
    """Materialized per-example gradients (B leading on every leaf)."""
    if strategy == "naive":
        return naive_per_example_grads(apply_fn, params, batch)
    if strategy == "multi":
        return multi_per_example_grads(apply_fn, params, batch)
    if strategy == "crb":
        return crb_per_example_grads(apply_fn, params, batch, **kw)
    raise ValueError(
        f"strategy {strategy!r} does not materialize per-example grads")
