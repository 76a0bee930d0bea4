"""Planned (auto) vs fixed strategies: wall time of the full DP-SGD
gradient, emitted to BENCH_strategies.json.

CPU-scaled shapes (the paper's claims are ratio claims); every timed step
returns the gradient pytree so XLA cannot dead-code-eliminate the clipped
sum.  ``auto`` must be no slower than the best fixed strategy on every
config — the planner's whole point is dominating any global choice.

    PYTHONPATH=src python -m benchmarks.strategies_bench [out.json]

``--mesh data:N`` benchmarks the sharded engine instead: auto planned
*with* the mesh (collective-aware plan + explicitly sharded execution)
vs auto planned *without*, on alexnet + llama32_1b, recording which
per-layer decisions the mesh flipped.  On a CPU host the device count is
forced to N before jax initializes.

    PYTHONPATH=src python -m benchmarks.strategies_bench --mesh data:8

A 2D spec (``--mesh data:4,model:2``) runs the same sweep tensor-sharded:
params partitioned over the ``model`` axis via the models' param-axes
trees, entries keyed ``{config}@data:4,model:2`` carrying the per-axis
predicted collective bytes and the calibrated ``planner_verdict``.
"""
from __future__ import annotations

import json
import os
import sys

if __name__ == "__main__":
    # A --mesh data:N run on a CPU host needs N devices before the jax
    # backend initializes.
    from repro.launch.mesh import force_host_device_count_for
    force_host_device_count_for(sys.argv)

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.configs import get_config
from repro.core import ClipPolicy, DPConfig, PrivacyEngine
from repro.core.clipping import dp_gradient
from repro.models.registry import build_model

SETTINGS = {
    "alexnet": dict(kind="cnn", img=64, B=4,
                    strategies=("multi", "crb", "ghost", "bk")),
    "vgg16": dict(kind="cnn", img=32, B=2,
                  strategies=("crb", "ghost", "bk")),
    "llama32_1b": dict(kind="lm", seq=256, B=8,
                       strategies=("multi", "crb", "ghost", "bk")),
}


def _setup(name, s):
    rng = np.random.RandomState(0)
    if s["kind"] == "cnn":
        cfg = get_config(name).replace(img_size=s["img"], n_classes=10)
        model = build_model(cfg)
        batch = {"img": jnp.array(
                     rng.randn(s["B"], 3, s["img"], s["img"]), jnp.float32),
                 "label": jnp.array(rng.randint(0, 10, (s["B"],)))}
    else:
        cfg = get_config("llama3.2-1b").reduced()
        model = build_model(cfg)
        batch = {"tokens": jnp.array(
                     rng.randint(0, cfg.vocab, (s["B"], s["seq"]))),
                 "labels": jnp.array(
                     rng.randint(0, cfg.vocab, (s["B"], s["seq"])))}
    params, axes = model.init(jax.random.PRNGKey(0))
    return model, params, batch, axes


def run(out_path: str = "BENCH_strategies.json") -> dict:
    results: dict = {}
    for name, s in SETTINGS.items():
        model, params, batch, _ = _setup(name, s)
        fns = {}
        for strat in s["strategies"]:
            dpc = DPConfig(l2_clip=1.0, strategy=strat)

            def step(p, b, _c=dpc):
                loss, grad, _ = dp_gradient(model.apply, p, b, cfg=_c)
                return loss, grad

            fns[strat] = jax.jit(step)
        # "auto" is timed through the production surface: a PrivacyEngine
        # whose jitted gradient closes over the ExecPlan.
        engine = PrivacyEngine(model.apply, params, batch,
                               dp=DPConfig(l2_clip=1.0, strategy="auto"))
        fns["auto"] = jax.jit(
            lambda p, b, _e=engine: _e.noisy_grad(p, b)[:2])
        # Interleave repeats so host noise hits every strategy equally,
        # then keep each strategy's least-perturbed execution.
        reps = 5 if s["kind"] == "lm" else 3
        times = {k: float("inf") for k in fns}
        for rep in range(reps):
            for strat, f in fns.items():
                t = time_fn(f, params, batch, warmup=2 if rep == 0 else 0,
                            iters=5, reduce="min")
                times[strat] = min(times[strat], t)
        for strat, t in times.items():
            emit(f"strategies/{name}/{strat}", t, "")
        best_fixed = min(v for k, v in times.items() if k != "auto")
        ratio = times["auto"] / best_fixed
        results[name] = {
            "times_us": times,
            "best_fixed_us": best_fixed,
            "auto_vs_best_fixed": ratio,
            "regression": ratio > 1.0,
        }
        if ratio > 1.0:
            print(f"WARNING: auto slower than best fixed strategy on "
                  f"{name} (ratio {ratio:.3f})", flush=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    for name, rec in results.items():
        emit(f"strategies/{name}/auto_vs_best_fixed",
             rec["times_us"]["auto"],
             f"ratio={rec['auto_vs_best_fixed']:.3f}")
    return results


CLIP_CONFIGS = ("alexnet", "vgg16")


def run_clip_modes(out_path: str = "BENCH_strategies.json") -> dict:
    """Clipping-mode benchmark on the conv-heavy configs: the planned
    engine under flat vs per_layer vs stale clipping, steady state (the
    stale engine is stepped once outside the timer to leave bootstrap).
    Entries merge into the strategy benchmark's JSON under
    ``{config}@clip:{mode}`` keys; stale's fused single-pass plan should
    be no slower than flat — that is the mode's whole point."""
    results = {}
    if os.path.exists(out_path):
        results = json.load(open(out_path))
    for name in CLIP_CONFIGS:
        model, params, batch, _ = _setup(name, SETTINGS[name])
        opt0 = {"step": jnp.zeros(())}

        def ident_opt(grads, state, params, *, lr, weight_decay):
            return params, state

        engines = {
            "flat": PrivacyEngine(
                model.apply, params, batch, optimizer=ident_opt,
                dp=DPConfig(l2_clip=1.0, clipping="flat")),
            "per_layer": PrivacyEngine(
                model.apply, params, batch, optimizer=ident_opt,
                dp=DPConfig(l2_clip=1.0, clipping="per_layer")),
            "stale": PrivacyEngine(
                model.apply, params, batch, optimizer=ident_opt,
                dp=DPConfig(l2_clip=1.0, clipping="stale")),
        }
        # Steady state: step each engine once so the stale engine leaves
        # bootstrap (and every jit is compiled) before the timers run.
        for eng in engines.values():
            eng.private_step(params, opt0, batch)
        # The modes differ by a few percent at most, so the interleaved
        # min needs more samples than the strategy sweep to beat host
        # noise on a shared machine.
        times = {k: float("inf") for k in engines}
        for rep in range(5):
            for mode, eng in engines.items():
                t = time_fn(lambda p, b, _e=eng: _e.private_step(
                                p, opt0, b)[2],
                            params, batch, warmup=1 if rep == 0 else 0,
                            iters=8, reduce="min")
                times[mode] = min(times[mode], t)
        fused = sum(lp.fused
                    for lp in engines["stale"].plan().layers.values())
        for mode, t in times.items():
            key = f"{name}@clip:{mode}"
            results[key] = {
                "times_us": t,
                "vs_flat": t / times["flat"],
                "fused_layers": fused if mode == "stale" else 0,
            }
            emit(f"strategies/{key}", t,
                 f"ratio={t / times['flat']:.3f}")
        if times["stale"] > times["flat"]:
            print(f"WARNING: stale slower than flat on {name} "
                  f"(ratio {times['stale'] / times['flat']:.3f})",
                  flush=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    return results


def run_attn(out_path: str = "BENCH_strategies.json") -> dict:
    """Attention-block realization benchmark: the block-level ghost norm
    (layer-local recompute + Gram-style reduction; per-example attention
    gradients never materialized) vs the materializing ``pe`` baseline on
    the same ``dp_attn``-tapped model, plus the planned engine surface
    (whose plan should pick ghost for the attention blocks).  Entries
    merge into the strategy benchmark's JSON under ``{config}@dp_attn``;
    ghost no slower than pe is the acceptance bar."""
    from repro.core import clipped_grad_sum

    results = {}
    if os.path.exists(out_path):
        results = json.load(open(out_path))
    s = SETTINGS["llama32_1b"]
    rng = np.random.RandomState(0)
    cfg = get_config("llama3.2-1b").reduced().replace(dp_attn=True)
    model = build_model(cfg)
    batch = {"tokens": jnp.array(
                 rng.randint(0, cfg.vocab, (s["B"], s["seq"]))),
             "labels": jnp.array(
                 rng.randint(0, cfg.vocab, (s["B"], s["seq"])))}
    params, _ = model.init(jax.random.PRNGKey(0))

    def norm_fn(method):
        def f(p, b):
            _, gsum, _ = clipped_grad_sum(model.apply, p, b, l2_clip=1.0,
                                          strategy="ghost",
                                          overrides={"*attn": method})
            return gsum
        return jax.jit(f)

    engine = PrivacyEngine(model.apply, params, batch,
                           dp=DPConfig(l2_clip=1.0, strategy="auto"))
    fns = {"attn_ghost": norm_fn("ghost"),
           "attn_pe": norm_fn("pe"),
           "auto": jax.jit(lambda p, b, _e=engine: _e.noisy_grad(p, b)[:2])}
    times = {k: float("inf") for k in fns}
    for rep in range(3):
        for k, f in fns.items():
            t = time_fn(f, params, batch, warmup=2 if rep == 0 else 0,
                        iters=3, reduce="min")
            times[k] = min(times[k], t)
    plan = engine.plan()
    attn_methods = sorted({lp.norm_method
                           for lp in plan.layers.values()
                           if lp.kind == "attn"})
    ratio = times["attn_ghost"] / times["attn_pe"]
    key = "llama32_1b@dp_attn"
    results[key] = {
        "batch": s["B"], "seq": s["seq"],
        "times_us": times,
        "ghost_vs_materialize": ratio,
        "planned_attn_methods": attn_methods,
        "regression": ratio > 1.0,
    }
    for k, t in times.items():
        emit(f"strategies/{key}/{k}", t, "")
    emit(f"strategies/{key}/ghost_vs_materialize", times["attn_ghost"],
         f"ratio={ratio:.3f} planned={','.join(attn_methods)}")
    if ratio > 1.0:
        print(f"WARNING: attn ghost norm slower than materialize "
              f"(ratio {ratio:.3f})", flush=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    return results


MESH_CONFIGS = ("alexnet", "llama32_1b")


def run_mesh(spec: str, out_path: str = "BENCH_strategies.json",
             calibration: str | None = None) -> dict:
    """Sharded-engine benchmark: auto planned with the mesh (collective-
    aware costs + explicit NamedShardings) vs auto planned without, same
    global batch.  Entries merge into the strategy benchmark's JSON under
    ``{config}@{spec}`` keys.

    Each config then closes the calibration loop: the harness measures
    the wire on this mesh, the observed ``auto_mesh`` step is folded back
    via ``Calibration.retimed``, the engine re-plans under the measured
    constants, and the record carries the planner's calibrated verdict —
    either the plan flips to something faster, or the cost model proves
    unsharded right and the apparent "regression" was priced fiction from
    the analytic wire constant.  ``--calibration PATH`` pre-registers a
    saved blob (e.g. from ``kernels_bench --calibrate-only``) so the
    *initial* mesh plan is already calibrated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import calibrate
    from repro.core import costmodel
    from repro.launch.mesh import make_mesh_from_spec
    from repro.launch.sharding import batch_sharding, param_sharding

    mesh = make_mesh_from_spec(spec)
    axes = costmodel.mesh_axes(mesh)
    d = costmodel.mesh_data_size(axes)
    if calibration:
        calib = calibrate.load_or_fallback(calibration, mesh=axes)
        if calib is not None:
            calibrate.register(calib)
            print(f"[calibrate] registered {calib.digest()} "
                  f"(source={calib.source})", flush=True)
    results = {}
    if os.path.exists(out_path):
        results = json.load(open(out_path))
    for name in MESH_CONFIGS:
        s = dict(SETTINGS[name])
        s["B"] = -(-s["B"] // d) * d       # round up to a multiple of d
        model, params, batch, paxes = _setup(name, s)
        eng0 = PrivacyEngine(model.apply, params, batch,
                             dp=DPConfig(l2_clip=1.0, strategy="auto"))
        # param_axes makes a model axis real: on a 2D spec the mesh
        # engines run tensor-sharded (psum'd partial Grams over `model`);
        # on a pure-data mesh the axes tree is inert.
        eng1 = PrivacyEngine(model.apply, params, batch,
                             dp=DPConfig(l2_clip=1.0, strategy="auto"),
                             mesh=mesh, param_axes=paxes)
        repl = NamedSharding(mesh, P())
        bsh = batch_sharding(batch, mesh)
        # On a 2D spec the timed boundary matches production layout:
        # params in (and the gradient out) partitioned over `model`.
        psh = (param_sharding(paxes, mesh, shapes_tree=params)
               if costmodel.mesh_model_axes(axes) else repl)
        fns = {
            "auto": jax.jit(lambda p, b, _e=eng0: _e.noisy_grad(p, b)[:2]),
            "auto_mesh": jax.jit(
                lambda p, b, _e=eng1: _e.noisy_grad(p, b)[:2],
                in_shardings=(psh, bsh), out_shardings=(repl, psh)),
        }
        times = {k: float("inf") for k in fns}
        for rep in range(3):
            for k, f in fns.items():
                t = time_fn(f, params, batch, warmup=2 if rep == 0 else 0,
                            iters=3, reduce="min")
                times[k] = min(times[k], t)
        p0, p1 = eng0.plan(), eng1.plan()
        s0, s1 = p0.sum_methods(), p1.sum_methods()
        flips = {n: {"without": [p0.layers[n].norm_method, s0[n]],
                     "with": [p1.layers[n].norm_method, s1[n]]}
                 for n in p0.layers
                 if (p0.layers[n].norm_method, s0[n])
                 != (p1.layers[n].norm_method, s1[n])}
        key = f"{name}@{spec}"
        results[key] = {
            "devices": d,
            "batch": s["B"],
            "times_us": times,
            "mesh_vs_nomesh": times["auto_mesh"] / times["auto"],
            "plan_flips": flips,
            "predicted_coll_mb_per_dev": p1.total_coll_bytes / 2**20,
            "predicted_coll_mb_per_dev_by_axis": {
                a: b / 2**20 for a, b in p1.total_coll_bytes_by_axis},
        }
        emit(f"strategies/{key}/auto_mesh", times["auto_mesh"],
             f"ratio={results[key]['mesh_vs_nomesh']:.3f} "
             f"flips={len(flips)}")

        # --- close the calibration loop: measure the wire, fold the
        # observed auto_mesh step back into the calibration, re-plan
        # under the measured constants, and record the verdict.
        calib0 = calibrate.lookup(axes)
        if calib0 is None:
            calib0 = calibrate.measure(mesh, quick=True)
        pred_s = costmodel.predicted_step_seconds(p1, calib0)
        calib1 = calib0.retimed(predicted_s=pred_s,
                                measured_s=times["auto_mesh"] / 1e6,
                                coll_bytes=p1.total_coll_bytes,
                                coll_bytes_by_axis=p1
                                .total_coll_bytes_by_axis)
        calibrate.register(calib1)
        eng2 = PrivacyEngine(model.apply, params, batch,
                             dp=DPConfig(l2_clip=1.0, strategy="auto"),
                             mesh=mesh, param_axes=paxes,
                             calibration=calib1)
        p2 = eng2.plan()
        verdict = costmodel.planner_verdict(p2, p0, calib1)
        plan_changed = p2.describe() != p1.describe()
        if plan_changed:
            f2 = jax.jit(lambda p, b, _e=eng2: _e.noisy_grad(p, b)[:2],
                         in_shardings=(psh, bsh), out_shardings=(repl, psh))
            t2 = time_fn(f2, params, batch, warmup=2, iters=3,
                         reduce="min")
        else:
            t2 = times["auto_mesh"]
        ratio_cal = t2 / times["auto"]
        results[key].update({
            "calibration": calib1.digest(),
            "planner_verdict": verdict,
            # per-axis view behind the verdict: what the calibrated plan
            # says each mesh axis carries, priced at that axis's wire
            "calibrated_coll_mb_per_dev_by_axis": {
                a: b / 2**20 for a, b in p2.total_coll_bytes_by_axis},
            "calibrated_plan_changed": plan_changed,
            "times_us_calibrated": t2,
            "mesh_vs_nomesh_calibrated": ratio_cal,
            "predicted_step_s": {
                "auto": costmodel.predicted_step_seconds(p0, calib1),
                "auto_mesh": costmodel.predicted_step_seconds(p2, calib1),
            },
            # only a real regression if the calibrated planner still
            # claims sharded wins while the measurement disagrees
            "regression": verdict == "sharded" and ratio_cal > 1.0,
        })
        emit(f"strategies/{key}/calibrated", t2,
             f"verdict={verdict} ratio={ratio_cal:.3f} "
             f"plan_changed={plan_changed} calib={calib1.digest()}")
        if results[key]["regression"]:
            print(f"WARNING: calibrated planner claims sharded wins on "
                  f"{key} but measurement disagrees "
                  f"(ratio {ratio_cal:.3f})", flush=True)
    with open(out_path, "w") as f:
        json.dump(results, f, indent=1)
    return results


if __name__ == "__main__":
    argv = sys.argv[1:]
    spec, clip_modes, calib_path, rest, i = None, False, None, [], 0
    dp_attn = False
    while i < len(argv):
        a = argv[i]
        if a == "--dp-attn":
            dp_attn, i = True, i + 1
        elif a == "--mesh":
            if i + 1 >= len(argv):
                raise SystemExit("--mesh requires a spec, e.g. "
                                 "--mesh data:8")
            spec, i = argv[i + 1], i + 2
        elif a.startswith("--mesh="):
            spec, i = a.split("=", 1)[1], i + 1
        elif a == "--calibration":
            calib_path, i = argv[i + 1], i + 2
        elif a.startswith("--calibration="):
            calib_path, i = a.split("=", 1)[1], i + 1
        elif a == "--clip-modes":
            clip_modes, i = True, i + 1
        else:
            rest.append(a)
            i += 1
    out = rest[0] if rest else "BENCH_strategies.json"
    if spec:
        run_mesh(spec, out, calibration=calib_path)
    elif clip_modes:
        run_clip_modes(out)
    elif dp_attn:
        run_attn(out)
    else:
        run(out)
