"""DP-SGD gradient computation: clip, accumulate, noise.

The preferred entry point is :class:`repro.core.engine.PrivacyEngine`
(make-private-once, step-many); :func:`dp_gradient` remains as the
functional core the engine drives and as a thin compatibility shim for
pre-engine callers.

Distribution notes (pjit): the pipeline is written in the global view, so
under :class:`~repro.core.engine.PrivacyEngine`'s sharded ``private_step``
(batch sharded over the data axes, params replicated) XLA partitions it
automatically — per-example norms are computed on the shard holding the
example and the clip coefficients see the psum'd global norm; the clipped
gradient sum is all-reduced over the data axis like any gradient.  Noise
is generated from the one replicated key against the replicated gradient,
so every device adds the *same* draw — not independent per-shard noise
(which would inflate the variance by the shard count).  With params
partitioned over a model axis the *noise array itself* is sharded, which
is why this module pins the partitionable threefry implementation below:
every draw must be a pure function of (key, position), identical under
any layout.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Callable, Mapping

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import costmodel, strategies

# Legacy (non-partitionable) threefry generates different bits when XLA
# partitions a draw: a model-sharded noise array would silently differ
# from the single-device draw for the same key, breaking both the
# sharded == single-device equivalence and noise-replay across topology
# changes (elastic resume).  The partitionable implementation makes
# every draw a pure function of (key, position) — identical values under
# any sharding — so it is a correctness requirement here, not a tuning
# flag.
jax.config.update("jax_threefry_partitionable", True)

CLIP_MODES = ("flat", "per_layer", "stale")


@dataclasses.dataclass(frozen=True)
class ClipPolicy:
    """How per-example clip coefficients are derived and applied.

    Modes:
      * ``flat``      — one coefficient per example from the *total* grad
        norm: ``w_b = min(1, C / ‖g_b‖)``.  Today's default; exact.
      * ``per_layer`` — per-layer budgets ``C_l`` with ``Σ_l C_l² = C²``;
        each parameter group is clipped against its own norm,
        ``w_{l,b} = min(1, C_l / ‖g_{l,b}‖)``.  The clipped sum's L2
        sensitivity is still ``C`` (see
        :func:`repro.core.privacy.clipping_sensitivity`), so the noise
        calibration is unchanged.  A layer's coefficient depends only on
        its own norm — no cross-layer reduction — and the planner drops
        the shared weighted backward (it cannot realize per-layer
        weights in one backward).
      * ``stale``     — flat coefficients computed from the *previous*
        step's norms.  The norm → coefficient dependency disappears from
        inside the step, so every layer's norm and weighted contribution
        can be produced in a single pass over the captures — the fused
        ``gram_norm_fused`` Pallas path — and a steady-state step is
        exactly 1 forward + 1 backward with no phase barrier.  Exactness
        caveat: this step's contribution is bounded by ``C`` only under
        the *lagged* norms; the first engine step bootstraps with exact
        flat clipping.

    ``budgets`` (``per_layer`` only): ``"uniform"`` (``C_l = C/√L``),
    ``"auto"`` (the engine tracks per-layer norm quantiles host-side and
    re-splits every step), or a mapping of {group-key glob: relative
    weight} (first match wins, unmatched groups get weight 1; weights are
    normalized so ``Σ C_l² = C²``).  Group keys are ``"/"``-joined
    parameter paths (e.g. ``"blocks/fc"``).

    ``fused`` (``stale`` only): allow the planner to select the fused
    single-pass norm+contrib realizations.  ``fused=False`` forces the
    same realizations flat mode uses, making a stale step *bitwise*
    reproducible against a flat step fed the same norms (the oracle
    suite relies on this).

    ``quantile`` / ``ema``: the per-layer norm statistic and host-side
    decay driving ``budgets="auto"``.
    """

    mode: str = "flat"
    budgets: Any = "uniform"
    fused: bool = True
    quantile: float = 0.5
    ema: float = 0.9

    def __post_init__(self):
        if self.mode not in CLIP_MODES:
            raise ValueError(f"unknown clipping mode {self.mode!r}; "
                             f"choose from {CLIP_MODES}")
        if isinstance(self.budgets, str):
            if self.budgets not in ("uniform", "auto"):
                raise ValueError(
                    f"budgets must be 'uniform', 'auto', or a "
                    f"{{glob: weight}} mapping, got {self.budgets!r}")
        else:
            object.__setattr__(self, "budgets", tuple(
                (str(p), float(w)) for p, w in
                (self.budgets.items() if isinstance(self.budgets, Mapping)
                 else self.budgets)))

def as_clip_policy(clipping) -> ClipPolicy:
    if clipping is None:
        return ClipPolicy()
    if isinstance(clipping, ClipPolicy):
        return clipping
    if isinstance(clipping, str):
        return ClipPolicy(mode=clipping)
    raise TypeError(f"clipping must be a ClipPolicy or mode string, "
                    f"got {clipping!r}")


def resolve_budgets(policy: ClipPolicy, l2_clip: float, group_keys,
                    observed=None):
    """Per-group clip budgets ``C_l`` with ``Σ_l C_l² = C²`` (exactly, up
    to float rounding — property-tested).

    ``observed`` (per-group positive norm statistics, e.g. the engine's
    tracked quantiles) drives the ``"auto"`` split ``C_l ∝ q_l``; without
    it ``"auto"`` falls back to uniform.  Mapping budgets are glob-matched
    against the ``"/"``-joined group keys, first match wins.
    """
    from fnmatch import fnmatchcase
    G = len(group_keys)
    if G == 0:
        raise ValueError("no parameter groups to budget")
    if isinstance(policy.budgets, tuple):
        w = []
        for key in group_keys:
            for pat, wt in policy.budgets:
                if fnmatchcase(key, pat):
                    w.append(wt)
                    break
            else:
                w.append(1.0)
        w = np.asarray(w, np.float64)
    elif policy.budgets == "auto" and observed is not None:
        w = np.asarray(observed, np.float64)
    else:
        w = np.ones((G,), np.float64)
    w = np.maximum(w, 1e-12)
    b = l2_clip * w / np.sqrt(np.sum(w * w))
    return jnp.asarray(b, jnp.float32)


@dataclasses.dataclass(frozen=True)
class NormCfg:
    """Per-kind norm-realization knobs (all default to the planner's
    analytic choice).

    dense:     auto | gram | stream | rank1 | pallas
    embed:     auto | segsum | gram | pe
    conv:      auto | ghost | pe          (norm realization)
    conv_impl: auto | fgc | bgc | pallas  (materializing conv-grad impl;
               auto: on a TPU, MXU matmuls for plain convs, else fgc)
    mem_budget: bytes of per-example-grad / capture scratch tolerated —
        bounds the planner's materializing paths AND drives
        ``microbatches="auto"``.
    """

    dense: str = "auto"
    embed: str = "auto"
    conv: str = "auto"
    conv_impl: str = "auto"
    mem_budget: int = costmodel.STREAM_MEM_BUDGET


# Legacy-kwarg sentinel: distinguishes "caller did not pass conv_norm" from
# the historical conv_norm=None, which is itself deprecated (now = "auto").
_UNSET = object()


@dataclasses.dataclass(frozen=True, init=False)
class DPConfig:
    """Structured DP-SGD configuration.

    Replaces the seed-era string soup (norm_method / embed_norm / conv_impl
    / conv_norm threaded positionally): norm realizations live in a nested
    :class:`NormCfg`, and individual layers are pinned with ``overrides``
    ({tap-name glob: method}, first match wins).  ``microbatches`` may be
    ``"auto"``: the count is derived from the ExecPlan's per-layer
    peak-memory estimates against ``norm.mem_budget``.

    The legacy keyword arguments are still accepted (with a
    DeprecationWarning) and mapped onto ``norm``; the historical
    ``conv_norm=None`` sentinel is gone — it now means ``"auto"``, and the
    old ghost/bk materialize-always behaviour is an explicit
    ``NormCfg(conv="pe")`` away.
    """

    l2_clip: float = 1.0
    noise_multiplier: float = 0.0
    strategy: str = "auto"           # naive | multi | crb | ghost | bk | auto
    norm: NormCfg = NormCfg()
    overrides: tuple = ()            # ((tap-name glob, method), ...)
    microbatches: Any = 1            # int or "auto"
    delta: float = 1e-5
    clipping: ClipPolicy = ClipPolicy()

    def __init__(self, l2_clip: float = 1.0, noise_multiplier: float = 0.0,
                 strategy: str = "auto", norm: NormCfg | None = None,
                 overrides=(), microbatches: Any = 1, delta: float = 1e-5,
                 clipping: ClipPolicy | str | None = None,
                 *, norm_method: str | None = None,
                 embed_norm: str | None = None, conv_impl: str | None = None,
                 conv_norm: Any = _UNSET):
        norm = norm or NormCfg()
        clipping = as_clip_policy(clipping)
        if clipping.mode != "flat" and strategy not in ("auto", "bk"):
            raise ValueError(
                f"clipping mode {clipping.mode!r} requires strategy 'auto' "
                f"or 'bk' (got {strategy!r}): the ghost weighted backward "
                f"and the materializing strategies only realize one flat "
                f"coefficient per example")
        legacy = {"norm_method": norm_method, "embed_norm": embed_norm,
                  "conv_impl": conv_impl}
        if conv_norm is not _UNSET:
            legacy["conv_norm"] = conv_norm
        if any(v is not None for v in legacy.values()) \
                or conv_norm is not _UNSET:
            warnings.warn(
                "DPConfig(norm_method=/embed_norm=/conv_impl=/conv_norm=) "
                "is deprecated; use DPConfig(norm=NormCfg(...)) and "
                "overrides={...} (conv_norm=None now means 'auto')",
                DeprecationWarning, stacklevel=2)
            norm = dataclasses.replace(
                norm,
                dense=norm_method or norm.dense,
                embed=embed_norm or norm.embed,
                conv_impl=conv_impl or norm.conv_impl,
                conv=(norm.conv if conv_norm is _UNSET
                      else (conv_norm or "auto")))
        if not (microbatches == "auto"
                or (isinstance(microbatches, int) and microbatches >= 1)):
            raise ValueError(
                f"microbatches must be a positive int or 'auto', "
                f"got {microbatches!r}")
        object.__setattr__(self, "l2_clip", float(l2_clip))
        object.__setattr__(self, "noise_multiplier", float(noise_multiplier))
        object.__setattr__(self, "strategy", strategy)
        object.__setattr__(self, "norm", norm)
        object.__setattr__(self, "overrides",
                           costmodel.normalize_overrides(overrides))
        object.__setattr__(self, "microbatches", microbatches)
        object.__setattr__(self, "delta", float(delta))
        object.__setattr__(self, "clipping", clipping)

    # Read-only views under the old knob names, so pre-engine call sites
    # keep working during the migration.
    @property
    def norm_method(self) -> str:
        return self.norm.dense

    @property
    def embed_norm(self) -> str:
        return self.norm.embed

    @property
    def conv_impl(self) -> str:
        return self.norm.conv_impl

    @property
    def conv_norm(self) -> str:
        return self.norm.conv

    @property
    def planned(self) -> bool:
        """Whether the strategy executes an ExecPlan (ghost, bk, auto)
        rather than materializing every per-example gradient."""
        return self.strategy in costmodel.PLANNED_STRATEGIES

    def planner_opts(self) -> dict:
        """Keyword arguments for :func:`repro.core.costmodel.get_plan`."""
        return dict(norm_method=self.norm.dense, embed_method=self.norm.embed,
                    conv_norm=self.norm.conv, mem_budget=self.norm.mem_budget,
                    conv_impl=self.norm.conv_impl, overrides=self.overrides,
                    clip_mode=self.clipping.mode,
                    clip_fused=self.clipping.fused, strategy=self.strategy)


def add_noise(grad_sum, key, noise_multiplier: float, l2_clip: float):
    """Add N(0, (σC)²) noise per coordinate.  Noise is generated *and
    summed* in float32 — only the final result is cast back to the grad
    dtype, so low-precision (bf16) grads don't silently quantize the noise
    before it is applied."""
    if noise_multiplier == 0.0:
        return grad_sum
    from repro.analysis.markers import tag
    leaves, treedef = jax.tree.flatten(grad_sum)
    sigma = noise_multiplier * l2_clip
    noisy = []
    with jax.named_scope("dp.noise"):
        keys = jax.random.split(key, len(leaves))
        for g, k in zip(leaves, keys):
            noise = tag(sigma * jax.random.normal(k, g.shape, jnp.float32),
                        kind="noise", sigma=float(sigma),
                        noise_multiplier=float(noise_multiplier),
                        l2_clip=float(l2_clip))
            noisy.append((g.astype(jnp.float32) + noise).astype(g.dtype))
    return jax.tree.unflatten(treedef, noisy)


def resolve_microbatches(apply_fn, params, batch, cfg: DPConfig,
                         plan=None, mesh=None) -> int:
    """Resolve ``cfg.microbatches`` to a concrete count.  ``"auto"`` derives
    it from the full-batch ExecPlan's memory estimates (planned strategies
    only; the materializing ones have no plan to consult and run unsplit).
    ``mesh`` makes the consulted plan's estimates per-device, so the split
    is sized for a device's batch shard rather than the global batch."""
    m = cfg.microbatches
    if m != "auto":
        return int(m)
    if not cfg.planned:
        return 1
    if plan is None:
        plan = costmodel.get_plan(apply_fn, params, batch,
                                  mesh=mesh, **cfg.planner_opts())
    B = jax.tree.leaves(batch)[0].shape[0]
    return costmodel.auto_microbatches(plan, B, cfg.norm.mem_budget)


def dp_gradient(apply_fn: Callable, params, batch, *, cfg: DPConfig,
                key=None, denom: int | None = None, plan=None,
                clip_state: dict | None = None):
    """Full DP-SGD gradient:  (Σ_b clip(g_b) + σC·ξ) / denom.

    ``batch`` leaves have leading global batch B; with ``cfg.microbatches``
    > 1 the batch is split and scanned to bound activation memory (valid
    because clipping is per-example and accumulation a plain sum).
    ``microbatches="auto"`` derives the split from the ExecPlan's memory
    estimates.  ``plan`` injects a pre-built (possibly deserialized)
    ExecPlan; it must match the per-microbatch shapes *and* the clipping
    mode.

    ``clip_state`` threads the cross-step clipping state of non-flat
    :class:`ClipPolicy` modes (the engine owns this loop):
      * ``{"prev_norms_sq": (B,)}`` — ``stale``: the norms the lagged
        coefficients are computed from.  Absent → bootstrap: this call
        clips with exact flat coefficients (and a flat plan) and returns
        the norms to feed the next step.
      * ``{"budgets": (G,)}`` — ``per_layer`` with ``budgets="auto"``:
        the engine-tracked split.  Absent → the policy's static split
        (uniform / mapping) is resolved against the plan's groups.

    Returns (mean loss, gradient pytree, aux dict).  Mode-dependent aux:
    ``per_layer`` adds ``per_layer_norms`` (G, B), ``per_layer_clip_
    fraction`` (G,) and ``clip_budgets``; ``stale`` adds ``clip_fraction_
    lagged`` (the coefficients actually *applied* this step — the plain
    ``clip_fraction`` describes the current norms, i.e. next step's
    coefficients) and ``clip_state`` for threading.
    """
    B = jax.tree.leaves(batch)[0].shape[0]
    denom = denom or B
    policy = cfg.clipping
    clip_state = dict(clip_state or {})
    prev_ns = clip_state.get("prev_norms_sq")
    budgets = clip_state.get("budgets")
    bootstrap = policy.mode == "stale" and prev_ns is None
    if bootstrap:
        # No lagged norms yet: clip exactly (flat), under a flat plan —
        # the stale plan's fused realizations need coefficients entering
        # the pass.  The returned clip_state seeds the steady state.
        policy = ClipPolicy()
        cfg = dataclasses.replace(cfg, clipping=policy)
        plan = None
    m = cfg.microbatches
    if m == "auto":
        m = resolve_microbatches(apply_fn, params, batch, cfg, plan=plan)
        if m > 1:
            plan = None   # a caller-supplied plan was for the full batch

    def one_microbatch(mb, mb_plan, mb_prev_ns):
        losses, gsum, norms_sq, detail = strategies.clipped_grad_sum_detailed(
            apply_fn, params, mb, l2_clip=cfg.l2_clip, strategy=cfg.strategy,
            norm_method=cfg.norm.dense, conv_impl=cfg.norm.conv_impl,
            embed_method=cfg.norm.embed, conv_norm=cfg.norm.conv,
            overrides=cfg.overrides, mem_budget=cfg.norm.mem_budget,
            plan=mb_plan, clip_policy=policy, budgets=budgets,
            prev_norms_sq=mb_prev_ns)
        return losses, jax.tree.map(lambda g: g.astype(jnp.float32), gsum), \
            norms_sq, detail["group_norms_sq"], detail["budgets"]

    if m == 1:
        losses, gsum, norms_sq, group_ns, budgets_used = \
            one_microbatch(batch, plan, prev_ns)
    else:
        assert B % m == 0, f"batch {B} not divisible by microbatches {m}"
        mbs = jax.tree.map(lambda a: a.reshape((m, B // m) + a.shape[1:]),
                           batch)
        prev_mbs = (None if prev_ns is None
                    else prev_ns.reshape(m, B // m))

        def body(acc, xs):
            mb, mb_prev = xs
            losses, gsum, norms_sq, group_ns, bud = \
                one_microbatch(mb, plan, mb_prev)
            acc = jax.tree.map(jnp.add, acc, gsum)
            return acc, (losses, norms_sq, group_ns, bud)

        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        gsum, (losses, norms_sq, group_ns, buds) = jax.lax.scan(
            body, zeros, (mbs, prev_mbs))
        losses = losses.reshape(-1)
        norms_sq = norms_sq.reshape(-1)
        if group_ns is not None:
            # (m, G, B/m) -> (G, B): microbatches tile the example axis.
            group_ns = jnp.moveaxis(group_ns, 0, 1).reshape(
                group_ns.shape[1], -1)
        budgets_used = (None if buds is None
                        else jax.tree.map(lambda a: a[0], buds))

    if key is not None and cfg.noise_multiplier > 0:
        gsum = add_noise(gsum, key, cfg.noise_multiplier, cfg.l2_clip)
    grad = jax.tree.map(lambda g: g / denom, gsum)
    C = cfg.l2_clip
    aux = {
        "per_example_norms": jnp.sqrt(norms_sq + 1e-12),
        "clip_fraction": jnp.mean(
            (jnp.sqrt(norms_sq) > C).astype(jnp.float32)),
    }
    if policy.mode == "per_layer":
        # The flat-style scalar above would be silently wrong (it compares
        # the *total* norm against C while clipping happened per layer):
        # report per-layer fractions against the per-layer budgets, and
        # make the scalar their mean over (layer, example) pairs.
        clipped = (jnp.sqrt(group_ns + 1e-12)
                   > budgets_used[:, None]).astype(jnp.float32)
        aux["per_layer_norms"] = jnp.sqrt(group_ns + 1e-12)
        aux["per_layer_clip_fraction"] = jnp.mean(clipped, axis=1)
        aux["clip_fraction"] = jnp.mean(clipped)
        aux["clip_budgets"] = budgets_used
    elif policy.mode == "stale" or bootstrap:
        # ``clip_fraction`` above describes the *current* norms — the
        # coefficients the next step will apply.  What this step actually
        # applied is lagged; label it instead of reporting it wrongly.
        applied_ns = norms_sq if bootstrap else prev_ns
        aux["clip_fraction_lagged"] = jnp.mean(
            (jnp.sqrt(applied_ns) > C).astype(jnp.float32))
        aux["clip_state"] = {"prev_norms_sq": norms_sq}
    return jnp.mean(losses), grad, aux


def non_dp_gradient(apply_fn: Callable, params, batch):
    """Reference non-private gradient (mean loss) for overhead baselines."""
    from repro.core.tapper import Tapper

    def loss(p):
        return jnp.mean(apply_fn(p, batch, Tapper()))

    return jax.value_and_grad(loss)(params)
