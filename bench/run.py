"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Set-up builds the program's DP-SGD step for the cell (``PrivacyEngine``
with the cell's mesh), makes the weights and a ring of distinct batches from
the seed, and drives the compiled step through its first three steps, which
are compared with the plain reference after the window.  The window then
runs the same step in a closed loop with one step in flight for
``--seconds``: each step's batch goes from the host to the devices, step
i+1 is dispatched before the host waits for step i, and a step's time is
the interval between consecutive completions.  With ``--trace 1`` the
window runs under the JAX profiler, followed by a few steps of the
non-private step of the same model, and the per-layer metrics are read from
that trace.

The last line on stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``, and
last ``checks``, each number compared beside its limit; the same numbers
are the last lines on stderr.  Without the accelerator the cell asks for,
the run exits non-zero before measuring and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import functools  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
if str(HERE.parent) not in sys.path:
    sys.path.insert(0, str(HERE.parent))

from bench import spec  # noqa: E402

NONPRIVATE_STEPS = 5


class NoAccelerator(RuntimeError):
    """The machine lacks the chips the cell asks for."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache(jax, root: pathlib.Path = spec.ROOT) -> str:
    """JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``, a fixed path so that a later
    run in the same checkout finds what an earlier one compiled.  Every
    program is cached, however short its compile."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or \
        str(pathlib.Path(root) / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def cell_devices(jax, chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX's platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, "
                            f"{len(devs)} found")
    return devs[:chips]


def seed_key(jax, np, seed: int, purpose: int):
    """A raw PRNG key for one purpose (weights, data) from any whole-number
    seed, all of its bits used."""
    words = np.random.SeedSequence([seed, purpose]).generate_state(2)
    return jax.numpy.asarray(words, dtype=jax.numpy.uint32)


def make_batch(inputs: dict, batch: int, key):
    """One batch of the configuration's inputs: a float input drawn
    N(0, 1), an integer one uniform in [0, high)."""
    import jax
    import jax.numpy as jnp
    out = {}
    for name, key_i in zip(sorted(inputs), jax.random.split(key, len(inputs))):
        spec_i = inputs[name]
        shape = (batch,) + tuple(spec_i["shape"])
        if spec_i["dist"] == "normal":
            out[name] = jax.random.normal(key_i, shape,
                                          jnp.dtype(spec_i["dtype"]))
        elif spec_i["dist"] == "uniform_int":
            out[name] = jax.random.randint(key_i, shape, 0, spec_i["high"],
                                           jnp.dtype(spec_i["dtype"]))
        else:
            raise spec.SpecError(f"input {name!r}: unknown dist "
                                 f"{spec_i['dist']!r}")
    return out


class Program:
    """The system under test for one cell: the engine, its compiled step
    and its state, built once and driven by set-up and by the window."""

    def __init__(self, cell: spec.Cell, seed: int, devices, ref_mod):
        import jax
        import jax.numpy as jnp
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.core import (ClipPolicy, DPConfig, PrivacyAccountant,
                                PrivacyEngine)
        from repro.models.registry import build_model
        from repro.optim import adamw_init

        self.jax = jax
        cfg, tr = cell.config, cell.traffic
        self.cell, self.seed = cell, seed
        self.batch = tr["batch"]
        overrides = {k: tuple(v) if isinstance(v, list) else v
                     for k, v in cfg["program"]["overrides"].items()}
        pcfg = get_config(cfg["program"]["arch"]).replace(**overrides)
        self.model = build_model(pcfg)
        if tr["mesh"]:
            from repro.launch.mesh import make_mesh_from_spec
            self.mesh = make_mesh_from_spec(tr["mesh"])
        else:
            self.mesh = jax.sharding.Mesh(np.array(devices[:1]), ("data",))
        self.repl = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P(self.mesh.axis_names))
        n_dev = self.mesh.devices.size
        if n_dev != len(devices):
            raise spec.SpecError(f"mesh {tr['mesh']} has {n_dev} devices, "
                                 f"the cell {len(devices)}")

        # Weights: the reference's initialisation, one jitted call on the
        # devices, in the type they are trained in.
        self.init = jax.jit(functools.partial(
            ref_mod.init_params, cfg, dtype=jnp.dtype(cfg["dtype"])),
            out_shardings=self.repl)
        self.weights_key = seed_key(jax, np, seed, 0)
        params = self.init(self.weights_key)
        want = jax.eval_shape(lambda k: self.model.init(k)[0],
                              jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if jax.tree.map(lambda a: (a.shape, a.dtype), want) != got:
            raise spec.SpecError(
                f"config {cfg['name']!r} and the program's model "
                f"{pcfg.name!r} disagree on the parameter shapes")

        # Inputs: a ring of distinct batches, made on the devices in bulk
        # and kept on the host, as an input pipeline would hold them.
        gen = jax.jit(functools.partial(make_batch, cfg["inputs"],
                                        self.batch),
                      out_shardings=self.rows)
        dkey = seed_key(jax, np, seed, 1)
        self.ring = [jax.device_get(gen(jax.random.fold_in(dkey, i)))
                     for i in range(tr["ring"])]

        sigma = tr["noise_multiplier"]
        self.engine = PrivacyEngine(
            self.model.apply, params, self.ring[0],
            dp=DPConfig(l2_clip=tr["clip"]["l2_clip"], noise_multiplier=sigma,
                        strategy="auto",
                        clipping=ClipPolicy(mode=tr["clip"]["mode"])),
            optimizer=tr["optimizer"]["name"], lr=tr["optimizer"]["lr"],
            weight_decay=tr["optimizer"]["weight_decay"],
            accountant=PrivacyAccountant(
                sampling_rate=self.batch / tr["dataset_size"],
                noise_multiplier=sigma),
            mesh=self.mesh if tr["mesh"] else None, run_seed=seed,
            calibration="analytic")
        self.params = params
        self.opt = jax.jit(adamw_init, out_shardings=self.repl)(params)
        self.step = 0
        self.dispatch_s = []

    def feed(self):
        return self.jax.device_put(
            self.ring[self.step % len(self.ring)], self.rows)

    def dispatch(self, annotate=contextlib.nullcontext):
        """Feed and dispatch one private step; returns its loss (a future)."""
        with annotate("bench.feed"):
            batch = self.feed()
        t = time.perf_counter()
        with annotate("bench.dispatch"):
            self.params, self.opt, loss, _ = self.engine.private_step(
                self.params, self.opt, batch, step=self.step)
        self.dispatch_s.append(time.perf_counter() - t)
        self.step += 1
        return loss

    def checked_steps(self, n: int) -> dict:
        """The first ``n`` steps from the seed, through the window's own
        call and feed; what the check needs of them goes to the host."""
        jax = self.jax
        losses = []
        m1 = None
        for s in range(n):
            loss = self.dispatch()
            losses.append(float(loss))
            if s == 0:
                m1 = jax.device_get(self.opt["m"])
        self.dispatch_s.clear()
        return {"losses": losses, "m1": m1,
                "params": jax.device_get(self.params)}

    def pe_sizes(self, n_devices: int) -> frozenset:
        """Elements of one convolution layer's per-example weight
        gradients on one device: its batch share times the layer's
        weights (every 4-D parameter is a convolution's)."""
        share = self.batch // n_devices
        return frozenset(share * leaf.size
                         for leaf in self.jax.tree.leaves(self.params)
                         if leaf.ndim == 4)

    def nonprivate_step(self):
        """The same model, shapes and AdamW update without privacy: the
        mean-loss gradient by ``non_dp_gradient``, jitted here."""
        from repro.core.clipping import non_dp_gradient
        from repro.optim import adamw_update
        apply, opt = self.model.apply, self.cell.traffic["optimizer"]

        def step(params, state, batch):
            loss, grad = non_dp_gradient(apply, params, batch)
            params, state = adamw_update(
                grad, state, params, lr=opt["lr"],
                weight_decay=opt["weight_decay"])
            return params, state, loss

        return self.jax.jit(step)

    def run_nonprivate(self, step, n: int) -> None:
        """``n`` non-private steps from the current state, on one batch;
        the state they make is dropped."""
        params, state, batch = self.params, self.opt, self.feed()
        for _ in range(n):
            params, state, loss = step(params, state, batch)
        loss.block_until_ready()

    def window(self, seconds: float, annotate=contextlib.nullcontext):
        """The measured window: a closed loop with one step in flight.
        Returns (start, completion times, losses)."""
        done, losses = [], []
        start = time.perf_counter()
        with annotate("bench.window"):
            pending = self.dispatch(annotate)
            while True:
                nxt = self.dispatch(annotate)
                with annotate("bench.wait"):
                    pending.block_until_ready()
                done.append(time.perf_counter())
                losses.append(pending)
                pending = nxt
                if done[-1] - start >= seconds:
                    break
            with annotate("bench.wait"):
                pending.block_until_ready()
            done.append(time.perf_counter())
            losses.append(pending)
        return start, done, losses


def quantile(values, q: float) -> float:
    """The q-quantile, linear between order statistics."""
    v = sorted(values)
    pos = q * (len(v) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t0: float = T0, require_tpu: bool = True,
        root: pathlib.Path = spec.ROOT) -> dict:
    """One run of ``cell``; returns the result object.  Only the tests
    pass ``require_tpu=False``, to drive a run on the CPU."""
    import jax
    import numpy as np

    devices = cell_devices(jax, cell.chips, require_tpu)
    log(f"device {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; compile cache {use_compile_cache(jax, root)}")
    ref_mod = spec.load_reference(cell.config, root)
    limits = spec.limits(cell.name, root)
    tr = cell.traffic
    prog = Program(cell, seed, devices, ref_mod)
    checked = prog.checked_steps(tr["checked_steps"])
    log(f"checked steps: losses {checked['losses']}")
    np_step = None
    if trace:
        np_step = prog.nonprivate_step()
        prog.run_nonprivate(np_step, 1)

    trace_dir = None
    annotate = contextlib.nullcontext
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir)
        annotate = jax.profiler.TraceAnnotation
    setup_s = time.perf_counter() - t0
    start, done, losses = prog.window(seconds, annotate)
    if trace:
        with annotate("bench.nonprivate"):
            prog.run_nonprivate(np_step, NONPRIVATE_STEPS)
        jax.profiler.stop_trace()

    window_s = done[-1] - start
    steps = len(done)
    step_ms = [1e3 * (b - a) for a, b in zip([start] + done[:-1], done)]
    failed = sum(not np.isfinite(float(x)) for x in losses)
    stats = [d.memory_stats() for d in devices]
    peak = max(st["peak_bytes_in_use"] for st in stats) \
        if all(stats) else None      # the CPU keeps no memory stats
    metrics_all = {
        "samples_per_s": steps * prog.batch / window_s,
        "step_ms_p90": quantile(step_ms, 0.9),
        "peak_hbm_gib": None if peak is None else peak / 2**30,
        "setup_s": setup_s,
    }
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    log(f"window {window_s:.3f} s, {steps} steps, step ms median "
        f"{statistics.median(step_ms):.3f} p90 "
        f"{metrics_all['step_ms_p90']:.3f}")

    result = {"correct": False, "attempted": steps, "failed": failed}
    if trace:
        from bench import trace as tracemod
        ctx = tracemod.Context(
            cell=cell, devices=len(devices), batch=prog.batch, steps=steps,
            window_s=window_s, dispatch_s=list(prog.dispatch_s),
            peaks=spec.peaks(devices[0].device_kind, root)
            if require_tpu else {},
            trace=tracemod.load(trace_dir, len(devices),
                                prog.pe_sizes(len(devices)),
                                NONPRIVATE_STEPS))
        shutil.rmtree(trace_dir, ignore_errors=True)
        metrics = {}
        for m in cell.per_layer:
            value = spec.load_reader(m["name"], root).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        result["breakdown"] = ctx.trace.breakdown()
    else:
        metrics = {m["name"]: {"value": metrics_all[m["name"]],
                               "unit": m["unit"]} for m in cell.end_to_end
                   if metrics_all[m["name"]] is not None}
    result["metrics"] = metrics
    result["device"] = device

    # The check: the program's state is freed, then the reference follows
    # the checked steps on the same weights and batches.
    ring = prog.ring[:tr["checked_steps"]]
    weights_key, init = prog.weights_key, prog.init
    del prog, losses, np_step
    gc.collect()
    from bench import check
    params0 = init(weights_key)
    ref = ref_mod.Reference(cell.config, tr, devices).run(
        params0, ring, seed, tr["checked_steps"])
    values = check.compare(checked, ref, jax.device_get(params0), tr)
    correct, checks = check.judge(values, limits)
    result["correct"] = correct and failed == 0
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    src = spec.ROOT / "src"
    if str(src) not in sys.path:
        sys.path.insert(0, str(src))
    try:
        result = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        log(f"not run: {e}")
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r} "
              f"(worst at {c['at']})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
