"""End-to-end DP training driver with checkpoint/restart fault tolerance.

Runs on whatever devices exist (the CPU in tests, TPU chips in training —
the same code path: the mesh is just bigger).  The loop is plan → step →
account: one PrivacyEngine owns the ExecPlan, the jitted private step, and the
accountant; checkpointing, the straggler monitor, and chaos-monkey fault
injection wrap around it.  ``--mesh data:8`` plans mesh-aware (per-layer
collective-bytes costs, topology-keyed fingerprint — the plan table gains
a ``coll MB`` column) and runs the private step sharded over the data
axes; on a CPU host the device count is forced to match before jax loads.

Preemption safety: noise keys come from the engine's deterministic
stream (``fold_in(PRNGKey(--run-seed), step)``), and checkpoints persist
the full :class:`~repro.checkpoint.DPTrainState` — params, optimizer,
cross-step clip state, the accountant ledger, the plan fingerprint, and
the monitor — so a killed run resumes bit-identically (the differential
proof lives in tests/test_resume_equivalence.py).  Resuming with fewer
devices than the checkpoint's mesh re-plans automatically onto the
surviving topology while the ledger and noise stream continue unbroken.
``--chaos p`` drills the whole path with seeded per-step failures.

    PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b \
        --reduced --steps 50 --batch 8 --noise 0.8 --clip 1.0 \
        --ckpt-dir /tmp/ckpt --fail-at 20 --chaos 0.05 --mesh data:8
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

if __name__ == "__main__":
    # A --mesh data:N run on a CPU host needs N devices before the jax
    # backend initializes.
    from repro.launch.mesh import force_host_device_count_for
    force_host_device_count_for(sys.argv)

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer, DPTrainState
from repro.configs import get_config
from repro.core import (ClipPolicy, DPConfig, PrivacyAccountant,
                        PrivacyEngine, costmodel)
from repro.data import SyntheticImageDataset, SyntheticLMDataset
from repro.launch.compile_cache import use_compile_cache
from repro.models.registry import build_model
from repro.optim import adamw_init, cosine_schedule
from repro.runtime import ChaosMonkey, StepMonitor, WorkerFailure, \
    elastic_mesh_axes, run_with_restarts


def make_batch_fn(cfg, batch: int, seq: int):
    if cfg.family == "cnn":
        ds = SyntheticImageDataset(cfg.img_size, cfg.n_classes)

        def fn(step):
            idx = (np.arange(batch) + step * batch) % len(ds)
            return ds.batch(idx)
    elif cfg.family == "encdec":
        ds = SyntheticLMDataset(cfg.vocab, seq)

        def fn(step):
            idx = (np.arange(batch) + step * batch) % len(ds)
            b = ds.batch(idx)
            g = np.random.RandomState(step)
            return {"src_frames": g.randn(batch, seq // 2, cfg.d_model)
                    .astype(np.float32),
                    "tokens": b["tokens"][:, : seq // 2],
                    "labels": b["labels"][:, : seq // 2]}
    else:
        ds = SyntheticLMDataset(cfg.vocab, seq)

        def fn(step):
            idx = (np.arange(batch) + step * batch) % len(ds)
            return ds.batch(idx)
    return fn


@dataclasses.dataclass
class TrainRun:
    """What :func:`main` returns: the engine that ran, the per-step
    losses, and the per-step wall-clock seconds (each measured to the
    step's finished outputs; a segment's first step includes compiling)."""

    engine: PrivacyEngine
    losses: list
    step_seconds: list


def main(argv=None) -> TrainRun:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--noise", type=float, default=0.0)
    ap.add_argument("--strategy", default="auto",
                    choices=["naive", "multi", "crb", "ghost", "bk",
                             "auto"])
    ap.add_argument("--clip-mode", default="flat",
                    choices=["flat", "per_layer", "stale"],
                    help="clipping policy: flat (exact, default), "
                         "per_layer (per-layer budgets with sum C_l^2 = "
                         "C^2), or stale (lagged coefficients; fused "
                         "single-pass plan, 1 fwd + 1 bwd steady state)")
    ap.add_argument("--clip-budgets", default="uniform",
                    choices=["uniform", "auto"],
                    help="per_layer budget split: uniform, or auto "
                         "(tracked per-layer norm quantiles)")
    ap.add_argument("--microbatches", default=1,
                    type=lambda v: v if v == "auto" else int(v),
                    help="int, or 'auto' to derive from the plan's "
                         "peak-memory estimates")
    ap.add_argument("--mesh", default=None,
                    help="mesh spec, e.g. 'data:8': plan mesh-aware "
                         "(collective-bytes costs, topology-keyed "
                         "fingerprint) and run the step sharded over the "
                         "data axes")
    ap.add_argument("--explain", action="store_true",
                    help="print the per-layer execution plan and exit")
    ap.add_argument("--plan-json", default=None,
                    help="plan cache file: loaded if present (skips the "
                         "probe), written after planning otherwise")
    ap.add_argument("--calibration", default=None,
                    help="'analytic' plans from the analytic constants "
                         "(the explicit opt-out; meshes with model axes "
                         "otherwise auto-measure at first engine init); "
                         "measured cost constants: a calibration JSON "
                         "path (written by `python -m benchmarks."
                         "kernels_bench --calibrate-only`; unusable blobs "
                         "fall back to analytic constants with a named "
                         "warning), or the literal 'measure' to run the "
                         "microbenchmark harness at engine init")
    ap.add_argument("--mispredict-threshold", type=float, default=0.5,
                    help="relative measured-vs-predicted step time "
                         "divergence that triggers an automatic re-plan "
                         "(requires an active calibration and a planned "
                         "strategy: ghost, bk or auto); <= 0 disables")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--fail-at", type=int, nargs="*", default=[])
    ap.add_argument("--run-seed", type=int, default=0,
                    help="seed of the deterministic noise stream: step "
                         "n's noise key is fold_in(PRNGKey(run_seed), n), "
                         "so a resumed run replays exactly the noise an "
                         "uninterrupted run would draw")
    ap.add_argument("--chaos", type=float, default=0.0,
                    help="chaos drill: per-step failure probability "
                         "(seeded via --chaos-seed, so drills replay)")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--max-restarts", type=int, default=5)
    ap.add_argument("--restart-backoff", type=float, default=0.0,
                    help="base seconds of the jittered exponential "
                         "restart backoff")
    ap.add_argument("--restart-window", type=float, default=None,
                    help="budget --max-restarts over a sliding window of "
                         "this many seconds instead of the whole run")
    ap.add_argument("--delta", type=float, default=1e-5)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override reduced d_model (e.g. ~100M scale)")
    ap.add_argument("--layers", type=int, default=0)
    args = ap.parse_args(argv)
    print(f"[cache] compiled programs persist in {use_compile_cache()}")

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.d_model:
        cfg = cfg.replace(d_model=args.d_model,
                          d_ff=(args.d_model * 4 if cfg.d_ff else 0),
                          head_dim=max(args.d_model // max(cfg.n_heads, 1),
                                       8))
    if args.layers:
        cfg = cfg.replace(n_layers=args.layers)
    model = build_model(cfg)
    dpc = DPConfig(l2_clip=args.clip, noise_multiplier=args.noise,
                   strategy=args.strategy,
                   microbatches=args.microbatches, delta=args.delta,
                   clipping=ClipPolicy(mode=args.clip_mode,
                                       budgets=args.clip_budgets))
    batch_fn = make_batch_fn(cfg, args.batch, args.seq)
    n_data = 1 << 16
    acct = PrivacyAccountant(sampling_rate=args.batch / n_data,
                             noise_multiplier=args.noise)
    chaos = ChaosMonkey(fail_at_steps=args.fail_at, p=args.chaos,
                        seed=args.chaos_seed)
    ckpt = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if args.plan_json and os.path.exists(args.plan_json):
        n = costmodel.load_plan_store(args.plan_json)
        print(f"[plan] loaded {n} plan(s) from {args.plan_json}")

    # Elastic resume: when a checkpoint exists, its mesh is the *intent*;
    # the devices this process actually has are the constraint.  An
    # explicit --mesh wins; otherwise re-plan the checkpoint's mesh onto
    # the surviving devices (same model parallelism, largest feasible
    # data degree) instead of hard-failing on the fingerprint mismatch.
    stored_meta = None
    if ckpt and ckpt.latest_step() is not None:
        stored_meta = ckpt.read_meta()
    mesh = None
    if args.mesh:
        from repro.launch.mesh import make_mesh_from_spec
        mesh = make_mesh_from_spec(args.mesh)
        d = costmodel.mesh_data_size(costmodel.mesh_axes(mesh))
        if args.batch % d:
            raise SystemExit(f"--batch {args.batch} not divisible by the "
                             f"mesh's data-parallel degree {d}")
        print(f"[mesh] {costmodel.format_mesh(costmodel.mesh_axes(mesh))} "
              f"over {len(jax.devices())} devices")
    elif stored_meta and stored_meta.get("mesh_axes"):
        from repro.launch.mesh import make_mesh_from_spec
        stored_axes = tuple((n, int(s))
                            for n, s in stored_meta["mesh_axes"])
        live_axes = elastic_mesh_axes(stored_axes, len(jax.devices()),
                                      args.batch)
        if live_axes != stored_axes:
            print(f"[elastic] checkpoint mesh "
                  f"{costmodel.format_mesh(stored_axes)} -> "
                  f"{costmodel.format_mesh(live_axes)} on "
                  f"{len(jax.devices())} surviving devices (re-planning; "
                  f"ledger and noise stream continue)")
        if live_axes:
            mesh = make_mesh_from_spec(
                ",".join(f"{n}:{s}" for n, s in live_axes))
    params0, axes0 = model.init(jax.random.PRNGKey(0))
    # One monitor for the whole run: stragglers (and re-plan events)
    # survive restarts instead of being read off a fresh StepMonitor at
    # the end (and they survive *process* deaths too — the monitor rides
    # in the checkpoint).
    mon = StepMonitor()
    engine = PrivacyEngine(
        model.apply, params0, batch_fn(0), dp=dpc, optimizer="adamw",
        lr=lambda step: cosine_schedule(step, warmup=10, total=args.steps,
                                        peak=args.lr),
        weight_decay=0.01, accountant=acct, mesh=mesh, param_axes=axes0,
        run_seed=args.run_seed, calibration=args.calibration,
        mispredict_threshold=(args.mispredict_threshold
                              if args.mispredict_threshold > 0 else None),
        monitor=mon)
    if engine.calibration is not None:
        print(f"[calibrate] {engine.calibration.digest()} "
              f"(source={engine.calibration.source})")
    if args.explain or dpc.planned:
        print(engine.explain())
    if args.explain:
        return TrainRun(engine, [], [])
    if args.plan_json and not os.path.exists(args.plan_json):
        engine.save_plan(args.plan_json)
        print(f"[plan] wrote {args.plan_json}")

    mesh_axes_now = costmodel.mesh_axes(mesh)
    step_seconds = []

    def train_state(params, opt):
        return DPTrainState(
            params=params, opt=opt, clip_state=engine.clip_state_dict(),
            ledger=acct.state_dict(), plan_fingerprint=engine.fingerprint(),
            monitor=mon.state_dict(), run_seed=args.run_seed,
            mesh_axes=mesh_axes_now)

    def segment(restart_count):
        params = params0
        opt = adamw_init(params)
        start = 0
        if ckpt and ckpt.latest_step() is not None:
            st, at = ckpt.restore_state(params, opt, fallback=True)
            if st.run_seed is not None and st.run_seed != args.run_seed:
                raise SystemExit(
                    f"checkpoint noise stream run_seed={st.run_seed} != "
                    f"--run-seed {args.run_seed}: resuming would draw a "
                    f"different noise sequence than the run being resumed")
            if st.plan_fingerprint and \
                    st.plan_fingerprint != engine.fingerprint():
                # A mesh change is the one legitimate fingerprint drift:
                # cross-check by re-keying under the checkpoint's mesh.
                if st.plan_fingerprint != engine.fingerprint(
                        mesh=st.mesh_axes):
                    raise SystemExit(
                        "checkpoint plan fingerprint mismatch beyond the "
                        "mesh: model code, shapes, or DP config changed; "
                        "refusing to resume onto a different mechanism")
            params, opt = st.params, st.opt
            engine.load_clip_state(st.clip_state)
            if st.ledger is not None:
                acct.load_state_dict(st.ledger)
            if st.monitor is not None:
                mon.load_state_dict(st.monitor)
            start = at + 1
            print(f"[restore] resuming from step {start}")
        else:
            # From-scratch (re)start: params go back to params0, so the
            # ledger and cross-step clip state must go back too — a
            # restarted segment that kept counting would overstate ε and
            # clip with another run's lagged norms.
            engine.reset_clip_state()
            acct.reset()
        losses = []
        # First step of a segment (and of each re-planned jit) compiles;
        # its wall-clock says nothing about the steady state, so it is
        # not fed to the mispredict loop.
        skip_observe = True
        for step in range(start, args.steps):
            chaos.maybe_fail(step)
            mon.start()
            batch = jax.tree.map(jnp.asarray, batch_fn(step))
            params, opt, loss, aux = engine.private_step(
                params, opt, batch, step=step)
            # The step returns once it is enqueued: time it to its
            # finished outputs, or the re-plan loop and the straggler
            # monitor read the dispatch cost.
            jax.block_until_ready((params, opt, loss))
            dt = mon.stop(step)
            step_seconds.append(dt)
            if skip_observe:
                skip_observe = False
            else:
                ev = engine.observe_step_time(dt, step=step)
                if ev is not None:
                    skip_observe = True
                    print(f"[replan] step {step}: measured/predicted "
                          f"{ev.ratio:.2f}x — calibration "
                          f"{ev.old_calibration} -> {ev.new_calibration}, "
                          f"plan {'changed' if ev.plan_changed else 'kept'}")
            losses.append(float(loss))
            if step % 10 == 0 or step == args.steps - 1:
                # Under stale clipping the honest "what did this step
                # apply" metric is the lagged one; under per_layer the
                # scalar is the mean over (layer, example) pairs of the
                # per-layer fractions also present in aux.
                if "clip_fraction_lagged" in aux:
                    clip_msg = (f"clip_frac(lagged) "
                                f"{float(aux['clip_fraction_lagged']):.2f}")
                else:
                    clip_msg = f"clip_frac {float(aux['clip_fraction']):.2f}"
                print(f"step {step:4d} loss {float(loss):.4f} "
                      f"{clip_msg} {dt*1e3:.0f}ms"
                      + (f" [{engine.report()}]" if args.noise else ""))
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save_state_async(step, train_state(params, opt))
        if ckpt:
            ckpt.wait()
            ckpt.save_state(args.steps - 1, train_state(params, opt))
        return losses

    losses, restarts = run_with_restarts(
        segment, max_restarts=args.max_restarts,
        backoff_s=args.restart_backoff,
        restart_window_s=args.restart_window)
    print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f}), "
          f"restarts={restarts}, stragglers={len(mon.stragglers)}, "
          f"replans={len(mon.replans)}")
    if args.noise:
        print(engine.report())
    return TrainRun(engine, losses, step_seconds)


if __name__ == "__main__":
    main()
