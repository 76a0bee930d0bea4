import os
import sys
if not any(a == "--plan-json" or a.startswith("--plan-json=")
           for a in sys.argv):
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any other import: jax locks the device
# count at first initialization.  512 host devices back the production
# meshes (16x16 single-pod, 2x16x16 multi-pod).  The --plan-json smoke
# mode runs eagerly on default devices and skips the mesh entirely.

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, per device: HLO FLOPs and bytes
(``compiled.cost_analysis()``), the memory footprint
(``compiled.memory_analysis()``), and collective-traffic bytes parsed from
the post-SPMD compiled HLO (with best-effort while-loop trip-count
multipliers, since collectives inside a layer scan execute once per
layer).  Results append incrementally to a JSON file consumed by
``benchmarks/roofline.py``.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch olmo-1b \
        --shape train_4k --mesh both --out results/dryrun.json
"""
import argparse
import json
import re
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCH_IDS, SHAPES, get_config
from repro.core import DPConfig, NormCfg
from repro.core.clipping import dp_gradient
from repro.launch import sharding as shd
from repro.launch.mesh import make_production_mesh
from repro.models.registry import build_model
from repro.optim import adamw_init, adamw_update

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _split_computations(hlo: str):
    """-> list of (comp_name, [lines]); entry computation flagged."""
    comps, cur_name, cur_lines = [], None, []
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            if cur_name is not None:
                comps.append((cur_name, cur_lines))
            nm = re.match(r"^(?:ENTRY\s+)?%?([\w.\-]+)", line)
            cur_name = nm.group(1) if nm else "?"
            cur_lines = []
            if line.startswith("ENTRY"):
                cur_name = "__entry__:" + cur_name
        elif cur_name is not None:
            cur_lines.append(line)
    if cur_name is not None:
        comps.append((cur_name, cur_lines))
    return comps


def _while_multipliers(comps):
    """Transitive execution-count multipliers per computation (entry=1,
    while bodies x trip count; nested loops multiply)."""
    names = {name.split(":", 1)[-1]: lines for name, lines in comps}
    whiles = []  # (parent_comp, body, cond, trip)
    for name, lines in comps:
        clean = name.split(":", 1)[-1]
        for line in lines:
            m = re.search(r"\bwhile\(.*?condition=%?([\w.\-]+), "
                          r"body=%?([\w.\-]+)", line)
            if m:
                cond, body = m.group(1), m.group(2)
            else:
                m = re.search(r"\bwhile\(.*?body=%?([\w.\-]+), "
                              r"condition=%?([\w.\-]+)", line)
                if not m:
                    continue
                body, cond = m.group(1), m.group(2)
            trip = 1
            if cond in names:
                consts = [int(x) for x in re.findall(
                    r"constant\((\d+)\)", "\n".join(names[cond]))]
                if consts:
                    trip = max(consts)
            whiles.append((clean, body, trip))

    mult = {}
    for name, _ in comps:
        if name.startswith("__entry__:"):
            mult[name.split(":", 1)[1]] = 1
    for _ in range(12):  # fixpoint over nesting depth
        changed = False
        for parent, body, trip in whiles:
            if parent in mult:
                v = mult[parent] * trip
                if mult.get(body) != v:
                    mult[body] = v
                    changed = True
        if not changed:
            break
    return mult


def parse_collectives(hlo: str) -> dict:
    """Sum per-device result bytes of every collective, multiplying ops in
    while-body computations by the (transitively resolved) trip counts --
    collectives inside a layer scan execute once per layer per microbatch.

    The result shape is the traffic proxy (HLO operands are name-only
    references): exact for all-reduce/all-to-all/permute, the gathered size
    for all-gather (~= ring traffic), the pre-reduce sum for
    reduce-scatter.
    """
    comps = _split_computations(hlo)
    mult = _while_multipliers(comps)

    out = {c: {"count": 0, "bytes": 0} for c in COLLECTIVES}
    for name, lines in comps:
        clean = name.split(":", 1)[-1]
        m = mult.get(clean, 1)
        for line in lines:
            for op in COLLECTIVES:
                if f" {op}(" in line or f" {op}-start(" in line:
                    left = line.split(f" {op}", 1)[0]
                    if "=" in left:
                        left = left.split("=", 1)[1]
                    b = sum(_shape_bytes(d, s)
                            for d, s in _SHAPE_RE.findall(left))
                    out[op]["count"] += m
                    out[op]["bytes"] += b * m
                    break
    out["total_bytes"] = sum(v["bytes"] for v in out.values()
                             if isinstance(v, dict))
    return out


_DEF_RE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_OPNAME_RE = re.compile(r"^\s*(?:\([^)]*\)|[a-z0-9]+\[[0-9,]*\][^\s]*)\s*"
                        r"([\w\-]+)\(")
_SKIP_OPS = {"parameter", "constant", "tuple", "get-tuple-element", "while",
             "bitcast", "after-all", "iota", "partition-id", "replica-id"}


def parse_hlo_costs(hlo: str) -> dict:
    """Per-device FLOPs and HBM bytes from the scheduled HLO, with
    while-loop trip multipliers (XLA's ``cost_analysis()`` counts loop
    bodies once, which hides everything inside a layer scan).

    FLOPs: matmuls (``dot``: 2·|out|·K from the lhs contracting dims) and
    convolutions (2·|out|·|rhs|/O).  Elementwise FLOPs are ignored —
    matmul-dominant workloads, standard MFU-numerator convention.

    Bytes: Σ over scheduled top-level ops of (result + operand) bytes in
    the entry / while computations — fusion-internal values never touch
    HBM and are excluded by construction.
    """
    comps = _split_computations(hlo)
    mult = _while_multipliers(comps)

    # local (own-loop) trip count per body: tensors inside a scan body
    # whose leading dim equals the trip count are per-step *slices* of
    # loop-invariant stacks (scan xs / ys buffers) — count 1/trip of them.
    local_trip: dict[str, int] = {}
    names_l = {name.split(":", 1)[-1]: lines for name, lines in comps}
    for name, lines in comps:
        for line in lines:
            m = re.search(r"\bwhile\(.*?condition=%?([\w.\-]+), "
                          r"body=%?([\w.\-]+)", line)
            if not m:
                continue
            cond, body = m.group(1), m.group(2)
            trip = 1
            if cond in names_l:
                consts = [int(x) for x in re.findall(
                    r"constant\((\d+)\)", "\n".join(names_l[cond]))]
                if consts:
                    trip = max(consts)
            local_trip[body] = max(local_trip.get(body, 1), trip)

    # global symbol table: value name -> list of (dtype, dims) shapes
    shapes: dict[str, list] = {}
    for _, lines in comps:
        for line in lines:
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            nm, rest = dm.group(1), dm.group(2)
            om = _OPNAME_RE.match(rest)
            type_seg = rest[: om.start(1)] if om else rest.split(" ", 1)[0]
            shapes[nm] = _SHAPE_RE.findall(type_seg)

    def _tensor_bytes(ss, trip: int) -> float:
        total = 0.0
        for d, s in ss:
            b = _shape_bytes(d, s)
            dims = [int(x) for x in s.split(",") if x]
            if trip > 1 and dims and dims[0] == trip:
                b = b / trip       # per-step slice of a stacked buffer
            total += b
        return total

    flops = 0.0
    bytes_ = 0.0
    for name, lines in comps:
        clean = name.split(":", 1)[-1]
        is_entry = name.startswith("__entry__:")
        if not (is_entry or clean in mult):
            continue  # fusion bodies etc. are accounted at their call site
        m = mult.get(clean, 1)
        lt = local_trip.get(clean, 1)
        for line in lines:
            dm = _DEF_RE.match(line)
            if not dm:
                continue
            rest = dm.group(2)
            om = _OPNAME_RE.match(rest)
            if not om:
                continue
            op = om.group(1)
            if op in _SKIP_OPS:
                continue
            type_seg = rest[: om.start(1)]
            res_shapes = _SHAPE_RE.findall(type_seg)
            res_bytes = _tensor_bytes(res_shapes, lt)
            args_seg = rest[om.end(0):].split(")", 1)[0]
            operands = re.findall(r"%([\w.\-]+)", args_seg)
            opd_bytes = sum(_tensor_bytes(shapes.get(o, []), lt)
                            for o in operands)
            bytes_ += (res_bytes + opd_bytes) * m

            if op == "dot" and operands:
                out_elems = 1
                for d, s in res_shapes:
                    for x in s.split(","):
                        if x:
                            out_elems *= int(x)
                cdm = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", rest)
                k = 1
                lhs_shapes = shapes.get(operands[0], [])
                if cdm and lhs_shapes:
                    dims = [int(x) for x in cdm.group(1).split(",") if x]
                    lhs_dims = [int(x) for x in lhs_shapes[0][1].split(",")
                                if x]
                    for d in dims:
                        if d < len(lhs_dims):
                            k *= lhs_dims[d]
                flops += 2.0 * out_elems * k * m
            elif op == "convolution" and len(operands) >= 2:
                out_elems = 1
                for d, s in res_shapes:
                    for x in s.split(","):
                        if x:
                            out_elems *= int(x)
                rhs_shapes = shapes.get(operands[1], [])
                if rhs_shapes:
                    rdims = [int(x) for x in rhs_shapes[0][1].split(",")
                             if x]
                    o = max(rdims) if rdims else 1
                    per_out = 1
                    for x in rdims:
                        per_out *= x
                    # heuristic: output-feature dim is the rhs dim present
                    # in the result shape; fall back to dim 0.
                    o = rdims[0] if rdims else 1
                    flops += 2.0 * out_elems * (per_out / max(o, 1)) * m
    return {"flops": flops, "bytes": bytes_}



# ---------------------------------------------------------------------------


def cost_analysis_dict(compiled) -> dict:
    """Normalize ``compiled.cost_analysis()``: newer jax returns one dict,
    older releases a per-device list of dicts (or None pre-compile)."""
    ca = compiled.cost_analysis()
    if ca is None:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca)


def abstract_params(model):
    axes_box = []

    def params_only(k):
        params, axes = model.init(k)
        axes_box.append(axes)
        return params

    sds = jax.eval_shape(params_only, jax.random.PRNGKey(0))
    return sds, axes_box[0]


def cache_sharding(cfg, cache_sds, mesh, batch: int):
    """Heuristic cache specs: shard the batch dim over the data axes and an
    exact-n_kv-heads dim over the model axis when divisible."""
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    data_axes = data_axes if len(data_axes) > 1 else data_axes[0]
    model_size = mesh.shape["model"]

    def spec(leaf):
        dims, used_b, used_m = [], False, False
        for d in leaf.shape:
            if not used_b and batch > 1 and d == batch:
                dims.append(data_axes)
                used_b = True
            elif (not used_m and cfg.n_kv and d == cfg.n_kv
                  and d % model_size == 0):
                dims.append("model")
                used_m = True
            else:
                dims.append(None)
        while dims and dims[-1] is None:
            dims.pop()
        return NamedSharding(mesh, P(*dims))

    return jax.tree.map(spec, cache_sds)


def plan_collectives(model, params_sds, bspec, dpc, mesh) -> dict:
    """Planner-predicted per-layer collective bytes for a train cell —
    the analytic counterpart of the post-SPMD HLO collectives parsed from
    the compiled module, from one shape-only probe."""
    from repro.core import costmodel

    plan = costmodel.get_plan(model.apply, params_sds, bspec,
                              mesh=mesh, **dpc.planner_opts())
    return {
        "mesh": costmodel.format_mesh(tuple(plan.mesh)),
        "fingerprint": plan.fingerprint,
        "per_layer_bytes": {n: lp.coll_bytes
                            for n, lp in plan.layers.items()},
        "total_bytes": plan.total_coll_bytes,
    }


def build_cell(arch: str, shape_name: str, mesh, *, microbatches=None,
               overrides: dict | None = None, dp_overrides: dict | None = None):
    """Returns (step_fn, example_args_with_shardings, donate, info) for a
    cell; ``info`` carries the planner's predicted per-layer collective
    bytes for train cells.

    ``overrides``: ModelConfig fields (hillclimb knobs, e.g.
    prefill_last_only=True, moe_impl="einsum", remat=False).
    ``dp_overrides``: DPConfig fields (strategy, norm_method, embed_norm,
    microbatches).
    """
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    model = build_model(cfg)
    params_sds, axes = abstract_params(model)
    pshard = shd.param_sharding(axes, mesh, fsdp=cfg.fsdp,
                                shapes_tree=params_sds)
    params_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        params_sds, pshard)
    repl = NamedSharding(mesh, P())

    if shape.kind == "train":
        m = microbatches or (16 if cfg.fsdp else 8)
        dpkw = dict(l2_clip=1.0, noise_multiplier=1.0,
                    strategy="auto", microbatches=m)
        normkw = dict(embed="gram")  # gram = paper-faithful baseline
        # --dp-set accepts both new NormCfg names (dense/embed/conv/
        # conv_impl) and the legacy knob names.
        _legacy = {"norm_method": "dense", "embed_norm": "embed",
                   "conv_norm": "conv"}
        for k, v in (dp_overrides or {}).items():
            k = _legacy.get(k, k)
            if k in ("dense", "embed", "conv", "conv_impl", "mem_budget"):
                normkw[k] = "auto" if v is None else v
            else:
                dpkw[k] = v
        dpc = DPConfig(norm=NormCfg(**normkw), **dpkw)

        def train_step(params, opt, batch, key):
            loss, grad, aux = dp_gradient(model.apply, params, batch,
                                          cfg=dpc, key=key)
            params, opt = adamw_update(grad, opt, params, lr=1e-4,
                                       weight_decay=0.01)
            return params, opt, loss, aux["clip_fraction"]

        opt_sds = jax.eval_shape(adamw_init, params_sds)
        opt_shard = jax.tree.map(
            lambda l: (NamedSharding(mesh, P()) if l.ndim == 0 else None),
            opt_sds)
        # moments share the parameter shardings (ZeRO via FSDP specs)
        opt_in = {
            "m": jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sh), opt_sds["m"], pshard),
            "v": jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=sh), opt_sds["v"], pshard),
            "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=repl),
        }
        bspec = model.train_input_specs(shape)
        bshard = shd.batch_sharding(bspec, mesh)
        batch_in = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            bspec, bshard)
        key_in = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=repl)
        try:
            info = {"dp_plan": plan_collectives(model, params_sds, bspec,
                                                dpc, mesh)}
        except Exception as e:          # advisory: never fail the cell
            info = {"dp_plan": {"error": f"{type(e).__name__}: {e}"}}
        return train_step, (params_in, opt_in, batch_in, key_in), (0, 1), \
            info

    if shape.kind == "prefill":
        specs = model.prefill_input_specs(shape)
        bshard = shd.batch_sharding(specs, mesh)
        args = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            specs, bshard)

        if cfg.family == "encdec":
            def prefill_step(params, src, tokens):
                logits, cache = model.prefill(params, src, tokens,
                                              max_len=shape.seq_len // 2)
                return jnp.argmax(logits, -1).astype(jnp.int32), cache

            return prefill_step, (params_in, args["src_frames"],
                                  args["tokens"]), (), {}

        def prefill_step(params, tokens):
            logits, cache = model.prefill(params, tokens,
                                          max_len=shape.seq_len)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        return prefill_step, (params_in, args["tokens"]), (), {}

    # decode
    specs = model.decode_input_specs(shape)
    cshard = cache_sharding(cfg, specs["cache"], mesh, shape.global_batch)
    cache_in = jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        specs["cache"], cshard)
    tok_in = jax.ShapeDtypeStruct(
        specs["tokens"].shape, specs["tokens"].dtype,
        sharding=(NamedSharding(mesh, P()) if shape.global_batch == 1 else
                  jax.tree.leaves(shd.batch_sharding(
                      {"t": specs["tokens"]}, mesh))[0]))

    def serve_step(params, cache, tokens):
        logits, cache = model.decode_step(params, cache, tokens)
        return jnp.argmax(logits, -1).astype(jnp.int32), cache

    return serve_step, (params_in, cache_in, tok_in), (1,), {}


def run_cell(arch: str, shape_name: str, mesh_kind: str, *, save_hlo=None,
             overrides=None, dp_overrides=None):
    t0 = time.time()
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    with shd.mesh_rules(mesh):
        step, args, donate, info = build_cell(arch, shape_name, mesh,
                                              overrides=overrides,
                                              dp_overrides=dp_overrides)
        lowered = jax.jit(step, donate_argnums=donate).lower(*args)
    t1 = time.time()
    compiled = lowered.compile()
    t2 = time.time()
    ca = cost_analysis_dict(compiled)
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)
    costs = parse_hlo_costs(hlo)
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    rec = {
        "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "status": "ok",
        "lower_s": round(t1 - t0, 2), "compile_s": round(t2 - t1, 2),
        "flops_parsed": costs["flops"],
        "bytes_parsed": costs["bytes"],
        "flops_per_device": ca.get("flops"),
        "bytes_per_device": ca.get("bytes accessed"),
        "transcendentals": ca.get("transcendentals"),
        "memory": None if ma is None else {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
        },
        "collectives": coll,
        "hlo_chars": len(hlo),
    }
    if info.get("dp_plan"):
        rec["dp_plan_collectives"] = info["dp_plan"]
    return rec


def cells_for(arch: str):
    cfg = get_config(arch)
    for s in ("train_4k", "prefill_32k", "decode_32k", "long_500k"):
        if s == "long_500k" and not cfg.subquadratic:
            continue  # full-attention archs skip 512k decode (DESIGN.md)
        yield s


def _plan_smoke_batch(cfg, batch: int, seq: int):
    rng = np.random.RandomState(0)
    if cfg.family == "cnn":
        return {"img": jnp.asarray(
                    rng.randn(batch, 3, cfg.img_size, cfg.img_size),
                    jnp.float32),
                "label": jnp.asarray(rng.randint(0, cfg.n_classes, (batch,)))}
    return {"tokens": jnp.asarray(rng.randint(0, cfg.vocab, (batch, seq))),
            "labels": jnp.asarray(rng.randint(0, cfg.vocab, (batch, seq)))}


def plan_smoke(path: str, arch: str, batch: int = 2, seq: int = 16) -> int:
    """Serialized-plan round trip across processes.

    First invocation (file absent): plan via PrivacyEngine, run one eager
    clipped-grad step, write the plan + per-leaf gradient digests.  Second
    invocation (file present, i.e. a fresh process): load the plan store,
    then verify the engine executes with ZERO probes and reproduces the
    stored gradients bit-for-bit.
    """
    from repro.core import PrivacyEngine, costmodel
    from repro.core.tapper import STATS

    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    batch_d = _plan_smoke_batch(cfg, batch, seq)
    dp = DPConfig(l2_clip=1.0, strategy="auto")

    def digests(grad):
        import hashlib
        return {jax.tree_util.keystr(kp):
                hashlib.sha256(np.ascontiguousarray(
                    np.asarray(leaf)).tobytes()).hexdigest()
                for kp, leaf in jax.tree_util.tree_leaves_with_path(grad)}

    if os.path.exists(path):
        n = costmodel.load_plan_store(path)
        engine = PrivacyEngine(model.apply, params, batch_d, dp=dp)
        STATS.reset()
        _, grad, _ = engine.noisy_grad(params, batch_d)
        snap = STATS.snapshot()
        assert snap["probes"] == 0, \
            f"plan store missed — model was re-probed: {snap}"
        with open(path) as f:
            want = json.load(f)["grad_digest"]
        got = digests(grad)
        bad = {k: (want[k], got.get(k)) for k in want if want[k] != got.get(k)}
        assert not bad, f"loaded-plan gradients differ: {bad}"
        print(f"plan smoke OK: {n} plan(s) loaded, probes=0, "
              f"{len(got)} gradient digests identical")
    else:
        engine = PrivacyEngine(model.apply, params, batch_d, dp=dp)
        _, grad, _ = engine.noisy_grad(params, batch_d)
        costmodel.save_plan_store(path, [engine.plan()],
                                  extra={"grad_digest": digests(grad)})
        print(f"plan smoke: wrote plan + digests to {path} "
              f"(fingerprint {engine.plan().fingerprint})")
    return 0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--plan-json", default=None,
                    help="serialized-ExecPlan smoke: write plan + gradient "
                         "digests if the file is absent, else load it and "
                         "verify probe-free, bit-identical execution")
    ap.add_argument("--plan-arch", default="llama3.2-1b")
    ap.add_argument("--plan-batch", type=int, default=2)
    ap.add_argument("--plan-seq", type=int, default=16)
    ap.add_argument("--arch", nargs="*", default=ARCH_IDS)
    ap.add_argument("--shape", nargs="*", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--hlo-dir", default=None)
    ap.add_argument("--set", nargs="*", default=[], metavar="K=V",
                    help="ModelConfig overrides, e.g. prefill_last_only=True")
    ap.add_argument("--dp-set", nargs="*", default=[], metavar="K=V",
                    help="DPConfig overrides, e.g. strategy=bk "
                         "embed_norm=segsum norm_method=stream")
    ap.add_argument("--calibration", default=None,
                    help="measured-cost calibration JSON to pre-register "
                         "before planning (see `python -m "
                         "benchmarks.kernels_bench --calibrate-only`); "
                         "unusable blobs fall back to analytic constants "
                         "with a named warning")
    args = ap.parse_args()

    if args.calibration:
        from repro import calibrate
        calib = calibrate.load_or_fallback(args.calibration)
        if calib is not None:
            calibrate.register(calib)
            print(f"[calibrate] registered {calib.digest()} "
                  f"(source={calib.source})")

    if args.plan_json:
        return plan_smoke(args.plan_json, args.plan_arch,
                          batch=args.plan_batch, seq=args.plan_seq)

    def _parse_kv(items):
        out = {}
        for kv in items:
            k, v = kv.split("=", 1)
            if v in ("True", "False"):
                v = v == "True"
            else:
                try:
                    v = int(v)
                except ValueError:
                    try:
                        v = float(v)
                    except ValueError:
                        pass
            out[k] = v
        return out

    overrides = _parse_kv(args.set)
    dp_overrides = _parse_kv(args.dp_set)

    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    results = []
    if args.skip_existing and os.path.exists(args.out):
        results = json.load(open(args.out))
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") == "ok"}

    meshes = {"single": ["single"], "multi": ["multi"],
              "both": ["single", "multi"]}[args.mesh]
    for arch in args.arch:
        shapes = args.shape or list(cells_for(arch))
        for shape in shapes:
            for mk in meshes:
                if (arch, shape, mk) in done:
                    continue
                print(f"=== {arch} x {shape} x {mk}", flush=True)
                try:
                    hlo_path = None
                    if args.hlo_dir:
                        os.makedirs(args.hlo_dir, exist_ok=True)
                        hlo_path = os.path.join(
                            args.hlo_dir, f"{arch}_{shape}_{mk}.hlo")
                    rec = run_cell(arch, shape, mk, save_hlo=hlo_path,
                                   overrides=overrides,
                                   dp_overrides=dp_overrides)
                    print(f"    ok: compile={rec['compile_s']}s "
                          f"flops/dev={rec['flops_per_device']:.3e} "
                          f"coll={rec['collectives']['total_bytes']:.3e}B",
                          flush=True)
                except Exception as e:
                    rec = {"arch": arch, "shape": shape, "mesh": mk,
                           "status": "fail", "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                    print(f"    FAIL: {rec['error']}", flush=True)
                results = [r for r in results
                           if (r["arch"], r["shape"], r["mesh"])
                           != (arch, shape, mk)]
                results.append(rec)
                with open(args.out, "w") as f:
                    json.dump(results, f, indent=1)
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    print(f"done: {n_ok}/{len(results)} ok")


if __name__ == "__main__":
    main()
