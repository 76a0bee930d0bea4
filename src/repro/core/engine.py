"""PrivacyEngine: the plan-first DP-SGD public surface.

Make-private-once, step-many (the Opacus-style engine shape of Subramani
et al. 2020 and Lee & Kifer 2020): construct the engine once from the
model's ``apply_fn``, parameter/batch *shapes* and a :class:`DPConfig`;
the per-layer :class:`~repro.core.costmodel.ExecPlan` is then a
first-class value —

  * ``engine.plan()``          the frozen plan (built once, cached);
  * ``engine.explain()``       per-layer table of chosen norm/sum
                               realizations with predicted FLOPs/bytes;
  * ``plan.to_json()``         cross-process plan caching keyed on the
                               model+shape fingerprint (pre-load a store
                               with ``costmodel.load_plan_store`` and the
                               engine never pays a probe);
  * ``engine.microbatches()``  plan-driven ``microbatches="auto"`` from
                               the plan's peak-memory estimates;
  * ``engine.private_step()``  one jitted closure over the plan fusing
                               gradient + clip + noise + optimizer update,
                               with accountant bookkeeping on the host;
  * ``engine.noisy_grad()``    the eager/jit-composable gradient-only
                               path (what ``private_step`` jits).

The strategies ``ghost``, ``bk`` and ``auto`` each execute a plan.  Steady
state executes exactly one forward and one backward per step, plus one of
each for a plan that takes the shared weighted backward (always under
``ghost``; counters in :data:`repro.core.tapper.STATS`).  ``naive``,
``multi`` and ``crb`` materialize every per-example gradient and have no
plan.

Sharded execution: pass ``mesh=`` a ``jax.sharding.Mesh`` and the plan
is built mesh-aware (per-device memory, collective-bytes cost terms, the
mesh folded into the fingerprint) while ``private_step`` runs under
``jax.jit`` with explicit ``NamedSharding``s — the batch sharded over
the data axes, params/optimizer state/PRNG key replicated.  Per-example
norms are reduced globally by SPMD (clip coefficients see the psum'd
global norm) and the noise is generated from the one replicated key, so
every device adds the *same* noise instead of per-shard draws: the
sharded step equals the single-device step up to reduction order.  A
mesh *spec* ("data:8", axes dict) is also accepted for planning-only
use on hosts without the devices.

Under ``jax.profiler`` each phase of the step is named in the trace.  On
the device, the metadata of every operation carries its phase's
``jax.named_scope``: ``dp.capture`` (forward and backward with taps),
``dp.norm/<norm method>/<group>``, ``dp.clip``,
``dp.contrib/<sum method>/<group>`` (``dp.contrib/backward`` for the
shared weighted backward), ``dp.noise`` and ``dp.update``.  On the host,
``private_step`` writes the spans ``engine.private_step`` and, inside it,
``engine.noise_key``, ``engine.dispatch`` and ``engine.absorb_clip_aux``;
``engine.trace`` appears only while ``jax.jit`` (re)traces the step.
Without the profiler the scopes are metadata only and each span costs one
check.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Callable

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core import costmodel
from repro.core.clipping import (DPConfig, dp_gradient, resolve_budgets,
                                 resolve_microbatches)
from repro.core.privacy import PrivacyAccountant, clipping_sensitivity


def _spec_of(tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(tuple(a.shape), a.dtype), tree)


class KeyProvenanceError(ValueError):
    """An explicit PRNG key contradicts the engine's deterministic noise
    stream (``fold_in(PRNGKey(run_seed), step)``).  Raised instead of
    silently drawing from the wrong key: noise from an unaccounted stream
    breaks the replay guarantee the accountant ledger depends on."""


@dataclasses.dataclass(frozen=True)
class ReplanEvent:
    """One firing of the engine's mispredict loop: measured step time
    diverged from the calibrated prediction beyond the threshold, the
    calibration was retimed from the observation, and the plan was
    rebuilt under the new constants.  Surfaced in :meth:`explain` and
    (when a monitor is attached) in ``StepMonitor.replans``."""

    step: int                 # step the divergence was confirmed at (-1 unknown)
    ratio: float              # measured / predicted at trigger time
    predicted_s: float
    measured_s: float
    old_calibration: str      # digests
    new_calibration: str
    old_fingerprint: str
    new_fingerprint: str
    plan_changed: bool        # did any layer's realization actually flip


def _resolve_optimizer(optimizer) -> Callable:
    if callable(optimizer):
        return optimizer
    from repro.optim import adamw_update, sgdm_update
    table = {"adamw": adamw_update, "sgdm": sgdm_update}
    try:
        return table[optimizer]
    except KeyError:
        raise ValueError(f"unknown optimizer {optimizer!r}; pass one of "
                         f"{sorted(table)} or an update callable") from None


class PrivacyEngine:
    """Plan-first DP-SGD driver bound to one (model, batch shape, config).

    Parameters:
      apply_fn:   ``apply_fn(params, batch, tapper) -> (B,) losses``.
      params:     parameter pytree (arrays or ShapeDtypeStructs — only
                  shapes/dtypes are retained).
      batch_spec: an example batch (arrays or ShapeDtypeStructs) fixing
                  the step's batch shapes.
      dp:         :class:`DPConfig`.
      optimizer:  "adamw" | "sgdm" | ``update(grads, state, params, *, lr,
                  weight_decay) -> (params, state)``.
      lr:         learning rate, or a callable ``lr(opt_step) -> lr`` for
                  schedules (traced inside the jitted step).
      sampling_rate / accountant: privacy accounting — pass either the
                  Poisson sampling rate (an accountant is built) or an
                  existing :class:`PrivacyAccountant`.
      plan:       inject a pre-built or deserialized ExecPlan (must match
                  the model, shapes, and mesh; validated up front with
                  named-field errors and again at execution).
      mesh:       a ``jax.sharding.Mesh`` — plans become mesh-aware and
                  ``private_step`` runs sharded (batch over the data
                  axes, params/opt/key replicated).  A mesh *spec*
                  (``"data:8"``, axes dict/tuple) plans for that topology
                  without requiring the devices (no sharded execution).
      param_axes: the logical-axes pytree ``model.init`` returns next to
                  the params.  On a mesh with model axes this partitions
                  params (and congruent optimizer moments) per
                  ``launch.sharding.PARAM_RULES`` — tensor-sharded
                  dense/conv layers then *execute* sharded.  Ignored on
                  pure-data meshes.
      calibration: measured cost constants for planning.  ``None``
                  consults the process registry for (live hardware,
                  mesh); on a mesh with model axes a registry miss
                  auto-measures once per (hardware, mesh) per process
                  (a 2D plan priced from ``ANALYTIC_FALLBACK`` would
                  invent the data/model bandwidth ratio); pass
                  ``"analytic"`` to explicitly opt out and plan from the
                  analytic constants.  A ``repro.calibrate.Calibration``
                  is validated
                  strictly against the live hardware and this mesh
                  (named errors on mismatch); a path string loads a
                  stored blob *softly* — unusable blobs degrade to the
                  analytic constants with a
                  ``CalibrationFallbackWarning``; the literal
                  ``"measure"`` runs the microbenchmark harness now
                  (once per (hardware, mesh) per process).
      mispredict_threshold: relative divergence of measured vs predicted
                  step time that triggers an automatic re-plan (e.g.
                  ``0.5`` = re-plan beyond ±50%).  Feed measured step
                  wall-clock to :meth:`observe_step_time`; ``None``
                  disables the loop.  Re-plans retime the calibration
                  from the observation, rebuild the plan under the new
                  constants, and are surfaced in :meth:`explain`,
                  :attr:`replan_events`, and the attached ``monitor``.
      monitor:    a ``runtime.monitor.StepMonitor`` to surface re-plan
                  events in (``monitor.replans``).
      run_seed:   seed of the deterministic per-step noise stream: step
                  ``n``'s noise key is ``fold_in(PRNGKey(run_seed), n)``
                  (:meth:`noise_key`), a pure function of (run_seed, n)
                  — so a killed-and-resumed run replays *exactly* the
                  noise an uninterrupted run would have drawn, never a
                  fresh draw (which would break the accounted mechanism).
                  Pass ``step=`` to :meth:`private_step`/:meth:`noisy_grad`
                  to use the stream.
    """

    def __init__(self, apply_fn: Callable, params, batch_spec,
                 dp: DPConfig | None = None, *, optimizer="adamw",
                 lr=1e-3, weight_decay: float = 0.0,
                 sampling_rate: float | None = None,
                 accountant: PrivacyAccountant | None = None,
                 plan: costmodel.ExecPlan | None = None,
                 mesh=None, param_axes=None, run_seed: int | None = None,
                 calibration=None,
                 mispredict_threshold: float | None = 0.5,
                 monitor=None):
        self.apply_fn = apply_fn
        self.dp = dp if dp is not None else DPConfig()
        self._params_spec = _spec_of(params)
        self._batch_spec = _spec_of(batch_spec)
        self._update_fn = _resolve_optimizer(optimizer)
        self._optimizer_name = optimizer if isinstance(optimizer, str) else None
        self._opt_spec = None   # recorded lazily; see _record_opt_spec
        self._lr = lr
        self._weight_decay = weight_decay
        if accountant is None and sampling_rate is not None:
            accountant = PrivacyAccountant(
                sampling_rate=sampling_rate,
                noise_multiplier=self.dp.noise_multiplier)
        self.accountant = accountant
        self.mesh = mesh if isinstance(mesh, jax.sharding.Mesh) else None
        self._mesh_axes = costmodel.mesh_axes(mesh)
        self._param_axes = param_axes
        if self.mesh is not None:
            d = costmodel.mesh_data_size(self._mesh_axes)
            for kp, leaf in jax.tree_util.tree_leaves_with_path(
                    self._batch_spec):
                if leaf.shape and leaf.shape[0] % d:
                    raise ValueError(
                        f"batch leaf {jax.tree_util.keystr(kp)} leading dim "
                        f"{leaf.shape[0]} is not divisible by the mesh's "
                        f"data-parallel degree {d} "
                        f"({costmodel.format_mesh(self._mesh_axes)})")
        self._calibration = self._resolve_calibration_arg(calibration)
        self.mispredict_threshold = mispredict_threshold
        self._monitor = monitor
        self.replan_events: list[ReplanEvent] = []
        self._step_ema: float | None = None
        self._step_obs = 0
        if plan is not None and self.dp.planned:
            # Fail loudly *now* on a stale injected plan, naming the
            # offending field (mesh / batch / clip mode / calibration /
            # fingerprint).
            costmodel.check_plan_matches(
                plan, mesh=self._mesh_axes,
                batch_sig=costmodel._shape_sig(self._batch_spec),
                fingerprint=self._fingerprint(),
                clip_mode=self.dp.clipping.mode,
                calibration="" if self._calibration is None
                else self._calibration)
        self._plan = plan
        self.run_seed = run_seed
        self._run_key = (None if run_seed is None
                         else jax.random.PRNGKey(run_seed))
        # Cross-step clipping state: stale mode's lagged norms, and the
        # per-layer "auto" budget split tracked from observed norm
        # quantiles.  Device arrays where possible (no host sync on the
        # stale path).
        self._prev_norms_sq = None
        self._budgets = None
        self._budget_q = None

    # -- planning ----------------------------------------------------------

    def _resolve_calibration_arg(self, calibration):
        """See ``calibration`` in the class docstring: registry lookup /
        strict Calibration / ``"measure"`` / soft path load."""
        from repro import calibrate
        if calibration == "analytic":
            return None
        if calibration is None:
            calib = calibrate.lookup(self._mesh_axes)
            if calib is not None:
                return calib
            # 2D-mesh default: a fresh engine on a data×model mesh would
            # otherwise price the model axis from ANALYTIC_FALLBACK (the
            # PR-8 follow-up) — measure once per (hardware, mesh) per
            # process.  1D meshes keep the analytic default: their single
            # ring has no cross-axis ratio to get wrong, and measuring
            # would perturb plan fingerprints test/CI lanes pin.
            if (self.mesh is not None
                    and costmodel.mesh_model_axes(self._mesh_axes)):
                import warnings
                try:
                    return calibrate.get_or_measure(self._mesh_axes)
                except calibrate.CalibrationError as e:
                    warnings.warn(
                        f"auto-calibration for mesh "
                        f"{costmodel.format_mesh(self._mesh_axes)} failed "
                        f"({type(e).__name__}: {e}); planning with the "
                        f"analytic fallback constants",
                        calibrate.CalibrationFallbackWarning, stacklevel=2)
            return None
        if isinstance(calibration, calibrate.Calibration):
            calibration.validate_for(calibrate.hardware_signature(),
                                     self._mesh_axes)
            return calibration
        if calibration == "measure":
            return calibrate.get_or_measure(self._mesh_axes)
        return calibrate.load_or_fallback(str(calibration),
                                          mesh=self._mesh_axes)

    @property
    def calibration(self):
        """The calibration this engine plans under (``None`` = analytic
        fallback constants)."""
        return self._calibration

    def _planner_opts(self) -> dict:
        return dict(self.dp.planner_opts(), mesh=self._mesh_axes,
                    calibration=self._calibration)

    def _fingerprint(self) -> str:
        return costmodel.plan_fingerprint(
            self.apply_fn, self._params_spec, self._batch_spec,
            **self._planner_opts())

    def fingerprint(self, mesh=None) -> str:
        """The plan fingerprint for this engine's (model, shapes, config)
        — what a checkpoint pins.  ``mesh=`` re-keys it under a different
        topology: the elastic-resume cross-check, distinguishing "this
        checkpoint is the same run on another mesh" (re-plan and resume)
        from "the model or planner config changed" (refuse)."""
        if mesh is None:
            return self._fingerprint()
        opts = dict(self._planner_opts(), mesh=costmodel.mesh_axes(mesh))
        return costmodel.plan_fingerprint(
            self.apply_fn, self._params_spec, self._batch_spec, **opts)

    def plan(self) -> costmodel.ExecPlan:
        """The full-batch ExecPlan (built once; cache/store hits are free)."""
        if self._plan is None:
            self._plan = costmodel.get_plan(
                self.apply_fn, self._params_spec, self._batch_spec,
                **self._planner_opts())
        return self._plan

    # -- measured-cost feedback (the mispredict loop) ----------------------

    def predicted_step_seconds(self) -> float:
        """Calibrated prediction of one step's wall-clock under the
        current plan — what :meth:`observe_step_time` compares against."""
        return costmodel.predicted_step_seconds(self.plan(),
                                                self._calibration)

    def observe_step_time(self, seconds: float,
                          step: int | None = None) -> ReplanEvent | None:
        """Record one executed step's measured wall-clock.  An EMA of the
        observations is compared against :meth:`predicted_step_seconds`;
        when the relative divergence exceeds ``mispredict_threshold``
        (after ≥ 2 observations, so one compile-tainted step can't
        trigger), the calibration is retimed from the observation, the
        plan is rebuilt under the new constants, and the returned
        :class:`ReplanEvent` is appended to :attr:`replan_events` (and
        the attached monitor).  Returns ``None`` when no re-plan fired.
        Inert without a calibration or with ``mispredict_threshold=None``
        — the analytic constants carry no time unit worth trusting."""
        if (self.mispredict_threshold is None or self._calibration is None
                or not self.dp.planned):
            return None
        seconds = float(seconds)
        self._step_obs += 1
        self._step_ema = (seconds if self._step_ema is None
                          else 0.5 * self._step_ema + 0.5 * seconds)
        if self._step_obs < 2:
            return None
        predicted = self.predicted_step_seconds()
        ratio = self._step_ema / max(predicted, 1e-12)
        if abs(ratio - 1.0) <= self.mispredict_threshold:
            return None
        return self._replan(step, ratio, predicted, self._step_ema)

    def _replan(self, step, ratio, predicted_s, measured_s) -> ReplanEvent:
        """Retime the calibration from the observed divergence and
        rebuild the plan (and the jitted step) under the new constants."""
        from repro import calibrate
        old = self._calibration
        old_plan = self.plan()
        new = old.retimed(predicted_s=predicted_s, measured_s=measured_s,
                          coll_bytes=old_plan.total_coll_bytes,
                          coll_bytes_by_axis=old_plan.total_coll_bytes_by_axis)
        calibrate.register(new)
        self._calibration = new
        self._plan = None
        self.__dict__.pop("_jit_step", None)
        self._step_ema = None
        self._step_obs = 0
        new_plan = self.plan()
        event = ReplanEvent(
            step=-1 if step is None else int(step), ratio=float(ratio),
            predicted_s=float(predicted_s), measured_s=float(measured_s),
            old_calibration=old.digest(), new_calibration=new.digest(),
            old_fingerprint=old_plan.fingerprint,
            new_fingerprint=new_plan.fingerprint,
            plan_changed=old_plan.describe() != new_plan.describe())
        self.replan_events.append(event)
        if self._monitor is not None:
            self._monitor.record_replan(event.step, event.ratio)
        return event

    def _explain_calibration(self) -> str:
        if self._calibration is None:
            lines = ["calibration: none — planning with the analytic "
                     "fallback constants (costmodel.ANALYTIC_FALLBACK)"]
        else:
            c = self._calibration
            coll = {a: f"{bw / 1e9:.1f} GB/s"
                    for a, bw in c.collective_bytes_per_second.items()}
            lines = [
                f"calibration: {c.digest()} (source={c.source}, hw="
                f"{c.hardware}) flops/s={c.flops_per_second:.3g} "
                f"hbm={c.hbm_bytes_per_second / 1e9:.1f} GB/s"
                + (f" collective={coll}" if coll else ""),
                f"predicted step: {self.predicted_step_seconds() * 1e6:.0f}"
                f" us; mispredict threshold: "
                + (f"±{self.mispredict_threshold:g}"
                   if self.mispredict_threshold is not None
                   else "disabled")]
        for ev in self.replan_events:
            lines.append(
                f"re-plan @ step {ev.step}: measured/predicted = "
                f"{ev.ratio:.2f}x ({ev.measured_s * 1e6:.0f} us vs "
                f"{ev.predicted_s * 1e6:.0f} us), calibration "
                f"{ev.old_calibration} -> {ev.new_calibration}, plan "
                + ("changed" if ev.plan_changed else "unchanged")
                + f" ({ev.old_fingerprint} -> {ev.new_fingerprint})")
        return "\n".join(lines)

    def explain(self) -> str:
        """Human-readable per-layer plan table (see ExecPlan.explain),
        plus the calibration block: active measured constants (or the
        analytic fallback), the predicted step time, the mispredict
        threshold, and every re-plan event fired so far."""
        clip = self.dp.clipping
        header = (f"PrivacyEngine: strategy={self.dp.strategy} "
                  f"C={self.dp.l2_clip} sigma={self.dp.noise_multiplier} "
                  f"clipping={clip.mode}"
                  + (f"(budgets={clip.budgets})"
                     if clip.mode == "per_layer" else "")
                  + f" microbatches={self.microbatches()}"
                  + ("" if self.dp.microbatches != "auto" else " (auto)")
                  + (f" mesh={costmodel.format_mesh(self._mesh_axes)}"
                     if self._mesh_axes else ""))
        cal = self._explain_calibration()
        if not self.dp.planned:
            return (header + f"\nmaterializing strategy {self.dp.strategy!r}:"
                    " no plan.\n" + cal)
        return header + "\n" + cal + "\n" + self.plan().explain()

    def save_plan(self, path: str):
        """Persist every plan this engine executes with — the full-batch
        plan and, when microbatching splits the step, the per-microbatch
        plan too — so a loading process never probes."""
        plans = [self.plan()]
        exec_plan = self._exec_plan()
        if exec_plan is not None \
                and exec_plan.fingerprint != plans[0].fingerprint:
            plans.append(exec_plan)
        costmodel.save_plan_store(
            path, plans,
            calibrations=[self._calibration] if self._calibration else None)

    def microbatches(self) -> int:
        """The resolved microbatch count (plan-driven for ``"auto"``) —
        the same resolution rule legacy ``dp_gradient`` applies."""
        plan = self._plan
        if self.dp.microbatches == "auto" and self.dp.planned:
            plan = self.plan()
        return resolve_microbatches(self.apply_fn, self._params_spec,
                                    self._batch_spec, self.dp, plan=plan,
                                    mesh=self._mesh_axes)

    def _exec_plan(self) -> costmodel.ExecPlan | None:
        """The plan matching the shapes the step actually executes: the
        full-batch plan, or a per-microbatch-shape plan when splitting."""
        if not self.dp.planned:
            return None
        m = self.microbatches()
        if m == 1:
            return self.plan()
        mb_spec = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (s.shape[0] // m,) + tuple(s.shape[1:]), s.dtype),
            self._batch_spec)
        return costmodel.get_plan(self.apply_fn, self._params_spec, mb_spec,
                                  **self._planner_opts())

    # -- execution ---------------------------------------------------------

    def noise_key(self, step: int):
        """Step ``step``'s noise key: ``fold_in(PRNGKey(run_seed), step)``.
        A pure function of (run_seed, step) — independent of how many
        times the process died and resumed on the way to ``step`` — so
        replayed steps re-add the *same* noise and the checkpointed
        accountant ledger stays the truth (deterministic replay releases
        nothing new)."""
        if self._run_key is None:
            raise ValueError(
                "engine has no noise stream; construct with run_seed=")
        return jax.random.key_data(jax.random.fold_in(self._run_key, step))

    def _check_key(self, key, step=None):
        if key is None and step is not None and self._run_key is not None:
            return self.noise_key(step)
        if key is None:
            if self.dp.noise_multiplier > 0:
                raise ValueError(
                    "noise_multiplier > 0 requires a PRNG key per step "
                    "(or construct the engine with run_seed= and pass "
                    "step=)")
            return jax.random.PRNGKey(0)
        if step is not None:
            # An explicit key together with step= claims to be the
            # stream's key for that step — verify, don't trust.
            if self._run_key is None:
                raise KeyProvenanceError(
                    f"key= passed with step={step} but the engine has no "
                    f"noise stream (construct with run_seed=) — cannot "
                    f"verify the key belongs to step {step}")
            data = key
            if isinstance(key, jax.core.Tracer):
                raise KeyProvenanceError(
                    f"key= passed with step={step} is a tracer — its "
                    f"provenance cannot be checked; pass step= alone and "
                    f"let the engine derive fold_in(run_key, {step})")
            if jnp.issubdtype(jnp.asarray(key).dtype, jax.dtypes.prng_key):
                data = jax.random.key_data(key)
            if not np.array_equal(np.asarray(data),
                                  np.asarray(self.noise_key(step))):
                raise KeyProvenanceError(
                    f"key= does not match the deterministic stream's key "
                    f"for step={step} (fold_in(PRNGKey({self.run_seed}), "
                    f"{step})) — replaying this step would draw different "
                    f"noise than the accounted run")
        return key

    def noisy_grad(self, params, batch, key=None, denom: int | None = None,
                   *, step: int | None = None):
        """(mean loss, noised clipped mean gradient, aux).  Eager — safe to
        call under an outer ``jax.jit``; ``private_step`` is the pre-jitted
        all-in-one.  Cross-step clipping state (stale norms, auto budgets)
        is threaded exactly as in ``private_step``.  ``step=`` draws the
        noise from the deterministic stream (``run_seed`` engines)."""
        cfg = dataclasses.replace(self.dp, microbatches=self.microbatches())
        out = dp_gradient(self.apply_fn, params, batch, cfg=cfg,
                          key=self._check_key(key, step), denom=denom,
                          plan=self._exec_plan(),
                          clip_state=self._clip_state())
        self._absorb_clip_aux(out[2])
        return out

    # -- cross-step clipping state ------------------------------------------

    def clip_state_dict(self) -> dict:
        """Host-side snapshot of the cross-step clipping state — the
        stale lagged norms and the per-layer auto-budget split + tracked
        quantiles.  This *must* ride in every checkpoint: a stale-mode
        restart without ``prev_norms_sq`` would re-run the flat bootstrap
        (different coefficients than the uninterrupted run), and an
        auto-budget restart without ``budget_q`` would re-split the clip
        budget from scratch — both silently change what the accounted
        mechanism released."""
        out = {}
        if self._prev_norms_sq is not None:
            out["prev_norms_sq"] = np.asarray(self._prev_norms_sq)
        if self._budgets is not None:
            out["budgets"] = np.asarray(self._budgets)
        if self._budget_q is not None:
            out["budget_q"] = np.asarray(self._budget_q)
        return out

    def load_clip_state(self, state: dict | None):
        """Install a checkpointed :meth:`clip_state_dict` (missing keys
        reset to empty — a flat-mode checkpoint carries none)."""
        state = dict(state or {})
        pn = state.get("prev_norms_sq")
        self._prev_norms_sq = None if pn is None else jnp.asarray(pn)
        b = state.get("budgets")
        self._budgets = None if b is None else jnp.asarray(b)
        q = state.get("budget_q")
        self._budget_q = None if q is None else np.asarray(q, np.float64)

    def reset_clip_state(self):
        """Drop all cross-step clipping state (a from-scratch restart:
        stale mode re-bootstraps, auto budgets re-track)."""
        self.load_clip_state(None)

    def _clip_state(self) -> dict:
        """The clip_state dict for the next step.  Structure changes only
        once (the stale bootstrap → steady transition), so ``jax.jit``
        retraces at most twice."""
        clip = self.dp.clipping
        if clip.mode == "stale" and self._prev_norms_sq is not None:
            return {"prev_norms_sq": self._prev_norms_sq}
        if clip.mode == "per_layer" and clip.budgets == "auto":
            if self._budgets is None:
                keys = tuple("/".join(str(p) for p in g.path)
                             for g in self.plan().groups)
                self._budgets = resolve_budgets(
                    clip, self.dp.l2_clip, keys, observed=self._budget_q)
            # The auto split must keep the clipped sum's sensitivity at C
            # (Σ C_l² = C²) or the σC noise calibration breaks.
            sens = clipping_sensitivity(self._budgets)
            if abs(sens - self.dp.l2_clip) > 1e-3 * self.dp.l2_clip:
                raise AssertionError(
                    f"auto budget split broke the sensitivity invariant: "
                    f"sqrt(sum C_l^2) = {sens} != C = {self.dp.l2_clip}")
            return {"budgets": self._budgets}
        return {}

    def _absorb_clip_aux(self, aux: dict):
        """Host-side bookkeeping after a step: thread stale norms, update
        the per-layer norm quantile EMA driving ``budgets="auto"``."""
        clip = self.dp.clipping
        leaves = jax.tree.leaves(aux)
        if leaves and isinstance(leaves[0], jax.core.Tracer):
            # noisy_grad under an outer jit: the caller owns the loop and
            # must thread the clip state itself — storing tracers as
            # cross-step state would poison the next eager step.
            return
        if clip.mode == "stale":
            self._prev_norms_sq = aux["clip_state"]["prev_norms_sq"]
        elif clip.mode == "per_layer" and clip.budgets == "auto":
            q = np.quantile(np.asarray(aux["per_layer_norms"], np.float64),
                            clip.quantile, axis=1)
            q = np.maximum(q, 1e-12)
            if self._budget_q is None:
                self._budget_q = q
            else:
                self._budget_q = clip.ema * self._budget_q \
                    + (1.0 - clip.ema) * q
            keys = tuple("/".join(str(p) for p in g.path)
                         for g in self.plan().groups)
            self._budgets = resolve_budgets(
                clip, self.dp.l2_clip, keys, observed=self._budget_q)

    def _step_fn(self):
        """The raw (unjitted) step closure over the plan — what
        ``private_step`` jits and what the static verifier traces."""
        cfg = dataclasses.replace(self.dp, microbatches=self.microbatches())
        plan = self._exec_plan()
        update_fn, lr, wd = self._update_fn, self._lr, self._weight_decay
        apply_fn = self.apply_fn

        def step(params, opt, batch, key, clip_state):
            # Runs only while jax.jit traces the step: a (re)trace shows in
            # a profile as this span.
            with TraceAnnotation("engine.trace"):
                loss, grad, aux = dp_gradient(
                    apply_fn, params, batch, cfg=cfg, key=key, plan=plan,
                    clip_state=clip_state)
                with jax.named_scope("dp.update"):
                    lr_t = lr(opt["step"]) if callable(lr) else lr
                    params, opt = update_fn(grad, opt, params, lr=lr_t,
                                            weight_decay=wd)
            return params, opt, loss, aux

        return step

    def _step_shardings(self):
        """(in_shardings, out_shardings) for the jitted step, or ``None``
        off-mesh.  Batch over the data axes; PRNG key, clip state, loss
        and aux replicated.  Params (and congruent optimizer moments) are
        replicated on a pure-data mesh; with ``param_axes=`` on a mesh
        that has model axes they are partitioned per the logical-axis
        rules (``launch.sharding.PARAM_RULES``), so tensor-sharded layers
        execute sharded: XLA inserts the partial-Gram / norm psums over
        ``model`` and the noise — drawn from the one replicated key, with
        value-semantic counter-based PRNG — lands sharded consistently
        with the param layout."""
        if self.mesh is None:
            return None
        from repro.launch.sharding import batch_sharding, param_sharding
        from jax.sharding import NamedSharding, PartitionSpec as P
        repl = NamedSharding(self.mesh, P())
        batch_sh = batch_sharding(self._batch_spec, self.mesh)
        if (self._param_axes is None
                or not costmodel.mesh_model_axes(self._mesh_axes)):
            return (repl, repl, batch_sh, repl, repl), repl
        param_sh = param_sharding(self._param_axes, self.mesh,
                                  shapes_tree=self._params_spec)
        # Optimizer moments inherit the param layout (ZeRO-style: every
        # moment shard lives once).  Custom optimizer callables have no
        # entry in the named table; their layout is derived from the
        # recorded state pytree instead (see _derived_opt_sharding).
        opt_sh = {"adamw": {"m": param_sh, "v": param_sh, "step": repl},
                  "sgdm": {"mom": param_sh, "step": repl},
                  }.get(self._optimizer_name)
        if opt_sh is None:
            opt_sh = self._derived_opt_sharding(param_sh, repl)
        return ((param_sh, opt_sh, batch_sh, repl, repl),
                (param_sh, opt_sh, repl, repl))

    def _record_opt_spec(self, opt):
        """Remember the optimizer-state structure so ``_step_shardings``
        can derive a layout for custom optimizer callables (the named
        table only covers adamw/sgdm).  Recorded once, from the first
        ``private_step``/``verify`` call — i.e. before the step closure
        is first jitted, so the derived shardings reach ``jax.jit``."""
        if opt is not None and self._opt_spec is None \
                and self._optimizer_name is None:
            self._opt_spec = _spec_of(opt)

    def _derived_opt_sharding(self, param_sh, repl):
        """Sharding for a custom optimizer callable's state, derived from
        its recorded state pytree: a leaf shaped like a param whose layout
        is unambiguous inherits that param's sharding (matching the
        adamw/sgdm moment treatment); scalars and ambiguous shapes stay
        replicated.  With no recorded spec the whole state is replicated
        — correct, just not partitioned."""
        if self._opt_spec is None:
            return repl
        by_shape = {}
        for leaf, sh in zip(jax.tree_util.tree_leaves(self._params_spec),
                            jax.tree_util.tree_leaves(param_sh)):
            shape = tuple(leaf.shape)
            cur = by_shape.get(shape, sh)
            by_shape[shape] = cur if cur == sh else None   # ambiguous

        def leaf_sh(leaf):
            shape = tuple(leaf.shape)
            sh = by_shape.get(shape) if shape else None
            return sh if sh is not None else repl

        return jax.tree_util.tree_map(leaf_sh, self._opt_spec)

    def _mesh_context(self):
        """The engine's mesh as JAX's current mesh while the step is traced
        and dispatched, so per-example kernels split their work over the
        data axes (``kernels.ops.per_example``)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    @functools.cached_property
    def _jit_step(self):
        step = self._step_fn()
        shardings = self._step_shardings()
        if shardings is None:
            return jax.jit(step)
        # Explicit shardings: per-example norms and the clipped sum reduce
        # globally under SPMD (flat clip coefficients see the psum'd
        # global norm; per-layer norms are psum'd the same way, per
        # group), and the noise is drawn from the one replicated key, so
        # each device adds identical noise rather than independent
        # per-shard draws.
        return jax.jit(step, in_shardings=shardings[0],
                       out_shardings=shardings[1])

    def verify(self, *, opt=None, raise_on_error: bool = False,
               coll_bytes_warn=None):
        """Statically verify this engine's private step (no execution):
        trace it to a jaxpr and check clip-before-reduce taint discipline,
        noise calibration and key hygiene, sharding invariants, and
        plan/graph consistency.  Returns a
        :class:`repro.analysis.report.VerifyReport`; with
        ``raise_on_error=True`` a failed report raises
        :class:`repro.analysis.report.DPVerificationError` instead."""
        from repro.analysis.verifier import verify_engine
        self._record_opt_spec(opt)
        report = verify_engine(self, opt=opt,
                               coll_bytes_warn=coll_bytes_warn)
        if raise_on_error:
            report.raise_if_failed()
        return report

    def private_step(self, params, opt, batch, key=None, *,
                     step: int | None = None):
        """One fused DP-SGD step: gradient + clip + noise + optimizer
        update in a single jitted closure over the plan, plus host-side
        accountant bookkeeping.  With a mesh the closure is jitted with
        explicit shardings (batch on the data axes; params, optimizer
        state, key, and outputs replicated).  Returns (params, opt, loss,
        aux).  ``step=`` (with a ``run_seed`` engine) draws the noise
        from the deterministic per-step stream instead of an explicit
        key — the restart-safe way to drive the loop.

        Non-flat clipping modes thread state across steps: ``stale``
        feeds this step's norms to the next step's coefficients (the
        first step bootstraps with exact flat clipping); ``per_layer``
        with ``budgets="auto"`` re-splits the budget from the tracked
        per-layer norm quantiles after every step."""
        with TraceAnnotation("engine.private_step"):
            self._record_opt_spec(opt)
            with TraceAnnotation("engine.noise_key"):
                key = self._check_key(key, step)
            clip_state = self._clip_state()
            with TraceAnnotation("engine.dispatch"), self._mesh_context():
                out = self._jit_step(params, opt, batch, key, clip_state)
            with TraceAnnotation("engine.absorb_clip_aux"):
                self._absorb_clip_aux(out[3])
            if self.accountant is not None:
                self.accountant.step()
        return out

    # -- accounting --------------------------------------------------------

    def epsilon(self, delta: float | None = None) -> float:
        if self.accountant is None:
            raise ValueError("engine has no accountant; pass sampling_rate=")
        return self.accountant.epsilon(delta if delta is not None
                                       else self.dp.delta)

    def report(self, delta: float | None = None) -> str:
        if self.accountant is None:
            return "DP: no accountant attached"
        return self.accountant.report(delta if delta is not None
                                      else self.dp.delta)
