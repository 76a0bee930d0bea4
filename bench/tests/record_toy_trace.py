"""Record ``data/toy_step.xplane.pb`` on one TPU chip:

    python3 bench/tests/record_toy_trace.py [--out <file.xplane.pb>]

The program's private step on the toy CNN of ``data/tiny.config.json``
(batch 8, AdamW, sigma 1), planned with one norm realization per layer
(``pe`` on conv0, ``ghost`` on conv1, ``rank1`` on fc0), is compiled and
warmed up, then traced as ``bench/run.py`` traces a window: three steps
inside ``bench.window``, each fed in ``bench.feed``, dispatched in
``bench.dispatch`` with the next step dispatched before the host waits in
``bench.wait`` for the one before, then two steps of the non-private step
in ``bench.nonprivate``.  To keep the file small the Python tracer is
off, and the ``/host:metadata`` plane (the compiled programs) and the
host lines that hold no ``bench.*`` or ``engine.*`` span are left out.
"""
from __future__ import annotations

import argparse
import glob
import json
import pathlib
import shutil
import sys
import tempfile

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

OVERRIDES = {"conv0": "pe", "conv1": "ghost", "fc0": "rank1"}
BATCH = 8
STEPS = 3
NONPRIVATE_STEPS = 2


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(num: int, val: bytes) -> bytes:
    return _varint(num << 3 | 2) + _varint(len(val)) + val


def slim(data: bytes) -> bytes:
    """The serialized XSpace without its ``/host:metadata`` plane, and
    without the host threads' lines that hold no ``bench.*`` or
    ``engine.*`` span; device planes are kept whole."""
    from bench.scopes import fields
    out = bytearray()
    for num, val in fields(data):
        if not isinstance(val, bytes):
            raise ValueError(f"XSpace field {num} is not a message")
        plane = list(fields(val)) if num == 1 else []
        name = next((v for n, v in plane if n == 2), b"")
        if name == b"/host:metadata":
            continue
        if name.startswith(b"/host:"):
            names = {}
            for n, v in plane:
                if n == 4:
                    entry = dict(fields(v))
                    names[entry.get(1, 0)] = dict(
                        fields(entry.get(2, b""))).get(2, b"")
            kept = bytearray()
            for n, v in plane:
                if n == 3 and not any(
                        names.get(dict(fields(ev)).get(1), b"").startswith(
                            (b"bench.", b"engine."))
                        for m, ev in fields(v) if m == 4):
                    continue
                kept += _field(n, v) if isinstance(v, bytes) else \
                    _varint(n << 3) + _varint(v)
            val = bytes(kept)
        out += _field(num, val)
    return bytes(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", default=str(HERE / "data" /
                                         "toy_step.xplane.pb"))
    args = ap.parse_args(argv)

    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from bench import run, scopes
    from repro.configs import get_config
    from repro.core import DPConfig, PrivacyEngine
    from repro.core.clipping import non_dp_gradient
    from repro.models.registry import build_model
    from repro.optim import adamw_init, adamw_update

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cfg = json.loads((HERE / "data" / "tiny.config.json").read_text())
    over = {k: tuple(v) if isinstance(v, list) else v
            for k, v in cfg["program"]["overrides"].items()}
    model = build_model(get_config(cfg["program"]["arch"]).replace(**over))
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.RandomState(0)
    batches = [{"img": rng.randn(BATCH, 3, 16, 16).astype(np.float32),
                "label": rng.randint(0, 10, (BATCH,)).astype(np.int32)}
               for _ in range(2)]
    engine = PrivacyEngine(
        model.apply, params, batches[0], run_seed=0,
        dp=DPConfig(l2_clip=1.0, noise_multiplier=1.0, overrides=OVERRIDES))
    opt = adamw_init(params)

    @jax.jit
    def nonprivate(params, state, batch):
        loss, grad = non_dp_gradient(model.apply, params, batch)
        params, state = adamw_update(grad, state, params, lr=1e-4,
                                     weight_decay=0.01)
        return params, state, loss

    step = 0

    def dispatch(params, opt, annotate):
        nonlocal step
        with annotate("bench.feed"):
            batch = jax.device_put(batches[step % 2])
        with annotate("bench.dispatch"):
            out = engine.private_step(params, opt, batch, step=step)
        step += 1
        return out

    # Warm-up: compile both steps outside the trace.
    params, opt, loss, _ = dispatch(params, opt, TraceAnnotation)
    nonprivate(params, opt, batches[0])[2].block_until_ready()
    loss.block_until_ready()

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    trace_dir = tempfile.mkdtemp(prefix="toy_trace_")
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with TraceAnnotation("bench.window"):
        params, opt, pending, _ = dispatch(params, opt, TraceAnnotation)
        for _ in range(STEPS - 1):
            params, opt, nxt, _ = dispatch(params, opt, TraceAnnotation)
            with TraceAnnotation("bench.wait"):
                pending.block_until_ready()
            pending = nxt
        with TraceAnnotation("bench.wait"):
            pending.block_until_ready()
    with TraceAnnotation("bench.nonprivate"):
        p, s, batch = params, opt, jax.device_put(batches[0])
        for _ in range(NONPRIVATE_STEPS):
            p, s, loss = nonprivate(p, s, batch)
        loss.block_until_ready()
    jax.profiler.stop_trace()

    found = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = pathlib.Path(found[0]).read_bytes()
    pathlib.Path(args.out).write_bytes(slim(data))
    shutil.rmtree(trace_dir, ignore_errors=True)
    tr = scopes.from_profile(args.out, 1, frozenset(), NONPRIVATE_STEPS)
    size = pathlib.Path(args.out).stat().st_size
    run.log(f"{args.out}: {size} bytes, {len(tr.ops)} device ops, "
            f"coverage {tr.coverage()}, scopes "
            f"{json.dumps({k: v * 1e3 for k, v in tr.scopes().items()})}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
