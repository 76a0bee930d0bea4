"""Plain reference of a DP-SGD step on a convolutional network.

It follows the configuration file's layer list (``convs``, ``pool``,
``fc``) in straightforward ``jax.numpy`` and imports nothing of the program
under test.  One step is:

  g_b      = grad of example b's cross-entropy loss       (batch of one)
  c_b      = min(1, C / ||g_b||)                          (flat clipping)
  S        = sum_b c_b g_b
  N        = sigma C xi,  xi ~ N(0, 1) per coordinate     (the engine's
             documented noise stream: step s draws from
             fold_in(PRNGKey(run_seed), s), split once per parameter leaf
             in tree order, one float32 normal draw per leaf)
  g        = (S + N) / B
  AdamW    m = b1 m + (1-b1) g;  v = b2 v + (1-b2) g^2;
           p -= lr ((m / (1-b1^t)) / (sqrt(v / (1-b2^t)) + eps) + wd p)

The per-example gradients are taken one example at a time in a scan, so
that memory holds one gradient and the running sum.  With several devices
the examples are split over them and the partial sums added.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# Every draw must be a pure function of (key, position), as in the engine.
jax.config.update("jax_threefry_partitionable", True)


def _conv_out(h, k, s, p):
    return (h + 2 * p - k) // s + 1


def param_shapes(cfg: dict) -> dict:
    """{"conv<i>": {"w": (out, in, k, k), "b": (out,)}, ...,
    "fc<j>": {"w": (in, out), "b": (out,)}} for the configuration."""
    cin, side, _ = cfg["image"][0], cfg["image"][1], cfg["image"][2]
    pk, ps = cfg["pool"]["kernel"], cfg["pool"]["stride"]
    shapes = {}
    for i, (out, k, s, p, pool) in enumerate(cfg["convs"]):
        shapes[f"conv{i}"] = {"w": (out, cin, k, k), "b": (out,)}
        side = _conv_out(side, k, s, p)
        if pool:
            side = _conv_out(side, pk, ps, 0)
        cin = out
    dims = [cin * side * side] + list(cfg["fc"]) + [cfg["n_classes"]]
    for j in range(len(dims) - 1):
        shapes[f"fc{j}"] = {"w": (dims[j], dims[j + 1]), "b": (dims[j + 1],)}
    return shapes


def init_params(cfg: dict, key, dtype=jnp.float32):
    """torchvision's VGG initialisation: convolutions Kaiming-normal over
    fan-out, linear layers N(0, 0.01), biases zero."""
    shapes = param_shapes(cfg)
    names = sorted(shapes)
    keys = jax.random.split(key, len(names))
    out = {}
    for name, k in zip(names, keys):
        w = shapes[name]["w"]
        if name.startswith("conv"):
            std = (2.0 / (w[0] * w[2] * w[3])) ** 0.5
        else:
            std = 0.01
        out[name] = {"w": (std * jax.random.normal(k, w, jnp.float32)
                           ).astype(dtype),
                     "b": jnp.zeros(shapes[name]["b"], dtype)}
    return out


def logits(cfg: dict, params, img):
    h = img
    pk, ps = cfg["pool"]["kernel"], cfg["pool"]["stride"]
    for i, (out, k, s, p, pool) in enumerate(cfg["convs"]):
        layer = params[f"conv{i}"]
        h = lax.conv_general_dilated(
            h, layer["w"], (s, s), [(p, p), (p, p)],
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        h = jax.nn.relu(h + layer["b"][None, :, None, None])
        if pool:
            h = lax.reduce_window(h, -jnp.inf, lax.max,
                                  (1, 1, pk, pk), (1, 1, ps, ps), "VALID")
    h = h.reshape(h.shape[0], -1)
    n_fc = len(cfg["fc"]) + 1
    for j in range(n_fc):
        h = h @ params[f"fc{j}"]["w"] + params[f"fc{j}"]["b"]
        if j < n_fc - 1:
            h = jax.nn.relu(h)
    return h


def losses(cfg: dict, params, img, label):
    """Per-example cross-entropy, in the dtype of the parameters."""
    logp = jax.nn.log_softmax(logits(cfg, params, img))
    return -jnp.take_along_axis(logp, label[:, None], 1)[:, 0]


def _clipped_sum_local(cfg, clip, params, img, label):
    """(sum_b c_b g_b, per-example losses) over the rows given, one
    example at a time."""
    def one(acc, ex):
        x, y = ex
        loss, g = jax.value_and_grad(
            lambda p: losses(cfg, p, x[None], y[None])[0])(params)
        sq = sum(jnp.sum(jnp.square(leaf)) for leaf in jax.tree.leaves(g))
        coef = jnp.minimum(1.0, clip / jnp.sqrt(sq)).astype(sq.dtype)
        acc = jax.tree.map(lambda a, gl: a + coef * gl, acc, g)
        return acc, loss

    zeros = jax.tree.map(jnp.zeros_like, params)
    return lax.scan(one, zeros, (img, label))


def noise(run_seed: int, step: int, shapes_tree, sigma: float):
    """The step's noise, float32, leaf for leaf in tree order."""
    key = jax.random.key_data(
        jax.random.fold_in(jax.random.PRNGKey(run_seed), step))
    leaves, treedef = jax.tree.flatten(shapes_tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [
        sigma * jax.random.normal(k, leaf.shape, jnp.float32)
        for leaf, k in zip(leaves, keys)])


def adamw(opt: dict, params, grad, step: int):
    """One AdamW update in the dtype of ``params``; ``opt`` holds lr, wd,
    b1, b2, eps."""
    lr, wd = opt["lr"], opt["weight_decay"]
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    t = step + 1
    bc1, bc2 = 1.0 - b1 ** t, 1.0 - b2 ** t

    def upd(p, m, v, g):
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        u = (m / bc1) / (jnp.sqrt(v / bc2) + eps) + wd * p
        return p - lr * u, m, v

    return upd


class Reference:
    """The reference DP-SGD run of one cell: ``steps`` steps from the
    given parameters on the given batches, in ``dtype``, over
    ``devices`` (examples split between them).

    ``fault`` plants one fault of the timed path in the reference, for
    reading what the check reports on it: ``"half_batch"`` sums the first
    half of the rows and divides by half the batch; ``"no_exchange"``
    sums only the first device's share of the rows, as a data-parallel
    step that leaves out the reduction between chips would."""

    def __init__(self, cfg: dict, traffic: dict, devices, *,
                 dtype=jnp.float32, fault: str | None = None):
        self.cfg, self.traffic = cfg, traffic
        self.dtype = dtype
        self.fault = fault
        # The reference's float32 is float32: on a TPU a float32 product
        # runs as one bfloat16 pass unless "highest" is asked for.
        self.precision = "highest" if dtype == jnp.float32 else "default"
        self.mesh = Mesh(np.array(devices), ("r",))
        self.repl = NamedSharding(self.mesh, P())
        self.rows = NamedSharding(self.mesh, P("r"))
        clip = traffic["clip"]["l2_clip"]
        body = functools.partial(_clipped_sum_local, cfg, clip)

        def clipped_sum(params, img, label):
            acc, loss = body(params, img, label)
            return jax.tree.map(lambda a: lax.psum(a, "r"), acc), loss

        self._clipped_sum = jax.jit(jax.shard_map(
            clipped_sum, mesh=self.mesh, in_specs=(P(), P("r"), P("r")),
            out_specs=(P(), P("r")), check_vma=False))
        self._update = jax.jit(self._update_fn, static_argnums=(4,))

    def _update_fn(self, params, m, v, grad, step):
        upd = adamw(self.traffic["optimizer"], params, grad, step)
        out = jax.tree.map(upd, params, m, v, grad)
        pick = lambda i: jax.tree.map(lambda t: t[i], out,
                                      is_leaf=lambda t: isinstance(t, tuple))
        return pick(0), pick(1), pick(2)

    def run(self, params0, batches, run_seed: int, steps: int):
        """Returns {"losses": [mean loss per step], "S0": step 0's clipped
        sum (float32), "N0": step 0's noise, "m1": the AdamW first moment
        after step 0, "params": the parameters after ``steps`` (float32)},
        each tree as host arrays.  The result has what the check reads of
        the program, so a reference run can stand in the program's place."""
        t = self.traffic
        sigma = t["noise_multiplier"] * t["clip"]["l2_clip"]
        cast = lambda tree: jax.tree.map(lambda a: a.astype(self.dtype), tree)
        params = jax.device_put(cast(params0), self.repl)
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        out = {"losses": []}
        for s in range(steps):
            img, label = batches[s]["img"], batches[s]["label"]
            B = img.shape[0]
            rows, denom = B, B
            if self.fault == "half_batch":
                rows, denom = B // 2, B // 2
            elif self.fault == "no_exchange":
                rows = B // len(self.mesh.devices)
            if rows % len(self.mesh.devices):
                raise ValueError(f"{rows} rows do not split over "
                                 f"{len(self.mesh.devices)} devices")
            img = jax.device_put(jnp.asarray(img[:rows], self.dtype),
                                 self.rows)
            label = jax.device_put(label[:rows], self.rows)
            with jax.default_matmul_precision(self.precision):
                S, loss = self._clipped_sum(params, img, label)
            N = jax.device_put(noise(run_seed, s, params, sigma), self.repl)
            grad = jax.tree.map(
                lambda a, n: ((a.astype(jnp.float32) + n).astype(self.dtype)
                              / denom), S, N)
            out["losses"].append(float(jnp.mean(loss.astype(jnp.float32))))
            if s == 0:
                out["S0"] = jax.device_get(
                    jax.tree.map(lambda a: a.astype(jnp.float32), S))
                out["N0"] = jax.device_get(N)
            del S, N
            params, m, v = self._update(params, m, v, grad, s)
            if s == 0:
                out["m1"] = jax.device_get(
                    jax.tree.map(lambda a: a.astype(jnp.float32), m))
        out["params"] = jax.device_get(
            jax.tree.map(lambda a: a.astype(jnp.float32), params))
        return out
