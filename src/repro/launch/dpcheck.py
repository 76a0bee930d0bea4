"""Static DP-invariant checker: trace the private step, prove it, exit.

``dpcheck`` is the CI face of :mod:`repro.analysis`.  For every
``arch x clip-mode x mesh`` lane it builds the model reduced, constructs
a :class:`~repro.core.PrivacyEngine`, and calls ``engine.verify()`` —
which traces the jitted private step to a jaxpr and abstractly
interprets it, *without executing a single step*:

  * per-example taint: every released gradient is clipped before any
    cross-example reduction (all clip modes, incl. the fused gram path);
  * noise discipline: one fresh f32 Gaussian per released leaf at
    ``sigma = noise_multiplier * l2_clip``, keys chained to the step key;
  * sharding safety: batch data-sharded, params/opt state/key/clip state
    and outputs replicated, clip decisions global, noise drawn once;
  * plan/graph consistency: the ExecPlan's realizations actually appear
    in the traced graph, the STATS census matches, the fingerprint
    (which now folds in a hash of the model/core source) is stable.

Exit status is 1 if any lane reports an error (or, with
``--fail-on-warn``, a warning), so a CI job wired to this module is a
hard gate: a refactor that silently drops the clip, reuses a noise key,
or de-realizes a planned kernel fails the build before it can train.

    PYTHONPATH=src python -m repro.launch.dpcheck \
        --archs alexnet vgg16 llama3.2-1b \
        --clip-modes flat per_layer stale --mesh none data:8
"""
from __future__ import annotations

import argparse
import sys

if __name__ == "__main__":
    # ``--mesh data:8`` lanes need the devices to exist before the jax
    # backend initializes (same contract as launch.train).
    from repro.launch.mesh import force_host_device_count_for
    force_host_device_count_for(sys.argv)

import jax

from repro.configs import get_config
from repro.core import ClipPolicy, DPConfig, PrivacyEngine, costmodel
from repro.launch.train import make_batch_fn
from repro.models.registry import build_model


def _build_engine(arch: str, clip_mode: str, mesh_spec, *,
                  batch: int, seq: int, noise: float, clip: float,
                  run_seed: int, strategy: str,
                  dp_attn: bool = False) -> PrivacyEngine:
    cfg = get_config(arch).reduced()
    if dp_attn:
        cfg = cfg.replace(dp_attn=True)
    model = build_model(cfg)
    dpc = DPConfig(l2_clip=clip, noise_multiplier=noise, strategy=strategy,
                   clipping=ClipPolicy(mode=clip_mode))
    mesh = None
    if mesh_spec and mesh_spec != "none":
        from repro.launch.mesh import make_mesh_from_spec
        mesh = make_mesh_from_spec(mesh_spec)
        d = costmodel.mesh_data_size(costmodel.mesh_axes(mesh))
        if batch % d:
            raise SystemExit(f"--batch {batch} not divisible by the "
                             f"mesh's data degree {d}")
    batch_fn = make_batch_fn(cfg, batch, seq)
    params0, axes0 = model.init(jax.random.PRNGKey(0))
    return PrivacyEngine(model.apply, params0, batch_fn(0), dp=dpc,
                         optimizer="adamw", lr=1e-3, weight_decay=0.01,
                         mesh=mesh, param_axes=axes0, run_seed=run_seed,
                         calibration="analytic")


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="statically verify DP invariants of the private step")
    ap.add_argument("--archs", nargs="+", default=["alexnet"])
    ap.add_argument("--clip-modes", nargs="+", default=["flat"],
                    choices=["flat", "per_layer", "stale"])
    ap.add_argument("--mesh", nargs="+", default=["none"],
                    help="mesh specs per lane; 'none' = single device")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--clip", type=float, default=1.0)
    ap.add_argument("--noise", type=float, default=0.8)
    ap.add_argument("--run-seed", type=int, default=0)
    ap.add_argument("--dp-attn", action="store_true",
                    help="enable the block-level attention realization "
                         "(dp_attn=True) so attention lanes exercise the "
                         "attn ghost-norm path")
    ap.add_argument("--strategy", default="auto",
                    help="per-example gradient strategy (default auto); "
                         "ghost, bk and auto execute a plan, which the "
                         "plan/graph consistency pass checks")
    ap.add_argument("--coll-bytes-warn", type=int, default=None,
                    help="per-device collective-bytes warning threshold")
    ap.add_argument("--fail-on-warn", action="store_true",
                    help="treat warnings as failures too")
    ap.add_argument("-v", "--verbose", action="store_true",
                    help="print every finding, not just failures")
    args = ap.parse_args(argv)

    lanes = [(a, m, s) for a in args.archs for m in args.clip_modes
             for s in args.mesh]
    failed = []
    for arch, mode, spec in lanes:
        name = f"{arch} clip={mode} mesh={spec}"
        if args.dp_attn:
            name += " dp_attn"
        # Lanes re-plan per topology; don't let a cached single-device
        # plan leak into a mesh lane or vice versa.
        costmodel.clear_plan_cache()
        engine = _build_engine(arch, mode, spec, batch=args.batch,
                               seq=args.seq, noise=args.noise,
                               clip=args.clip, run_seed=args.run_seed,
                               strategy=args.strategy,
                               dp_attn=args.dp_attn)
        report = engine.verify(coll_bytes_warn=args.coll_bytes_warn)
        bad = bool(report.errors) or (args.fail_on_warn
                                      and bool(report.warnings))
        status = "FAIL" if bad else "PASS"
        extra = ""
        if report.warnings and not bad:
            extra = f"  ({len(report.warnings)} warning(s))"
        print(f"[dpcheck] {status}  {name}{extra}")
        shown = report.findings if args.verbose else (
            report.errors + report.warnings if bad else report.warnings)
        for f in shown:
            print(f"    {f.severity:7s} {f.code:28s} {f.message}")
        if bad:
            failed.append(name)
    print(f"[dpcheck] {len(lanes) - len(failed)}/{len(lanes)} lanes clean")
    if failed:
        for name in failed:
            print(f"[dpcheck]   failed: {name}")
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
