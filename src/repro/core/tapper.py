"""Tap/capture engine for per-example gradient reconstruction.

The chain-rule-based (``crb``) strategy of Rochette et al. (2019) — and the
ghost / book-keeping extensions built on top of it — need, for every
parametric layer, two tensors per example:

  * the layer *input*  ``x_b``   (captured on the forward pass), and
  * the layer *output cotangent* ``δy_b = ∂L_b/∂y_b``.

Autodiff gives us cotangents of anything that is an *input* to the
computation, so every parametric layer adds a zero-valued "tap" to its
output::

    y = x @ W + taps[name]

Differentiating ``Σ_b L_b`` with respect to the taps yields every ``δy_b``
in one standard backward pass (examples are independent, so
``∂(Σ_b L_b)/∂y[b] = ∂L_b/∂y[b]``).  This module provides:

  * :class:`Tapper` — threaded through model ``apply`` functions; applies
    taps, records captures, registers static layer metadata.
  * :func:`scan_with_taps` — ``lax.scan`` over stacked layers with tap
    slicing and capture stacking (nested scans supported).
  * :func:`probe` — shape-only trace (``jax.eval_shape``) discovering tap
    shapes and layer metadata with zero allocation.
  * :func:`capture_backward` — the single backward pass yielding
    (per-example losses, captures, tap cotangents).

Shared parameters (tied embeddings, Zamba2's shared attention block) are
declared by prefixing the tap name with ``"~"``: the parameter path is then
interpreted from the params root and the layer is marked ``shared`` so the
strategies accumulate (and cross-correlate, for norms) all contributions to
the same parameter.

Models stay pure: a ``Tapper`` in mode ``"none"`` is a no-op, so the same
model code serves ordinary training, serving, and every PEG strategy.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

TAP_KEY = "__tap__"


# ---------------------------------------------------------------------------
# Pipeline instrumentation
#
# Counts *Python-level* executions of the expensive phases: model forwards,
# backward passes through the model, and shape probes.  Under ``jax.jit``
# these only tick at trace time; calling the strategies eagerly (as the
# tests do) counts real executions per step, which is how the
# one-forward/one-backward steady-state claim of the planned pipeline is
# verified against the 2+2 of the ghost path.


class PipelineStats:
    """Counters for forwards / backwards / probes through a model.

    ``fused`` additionally counts fused norm+contrib realizations
    (``gram_norm_fused``-backed single passes picked by stale-coefficient
    plans), and ``conv_impls`` the implementation each per-example conv
    gradient took (``pallas`` for the MXU kernel, ``taps`` for per-tap
    dots, ``s2d_pallas`` and ``s2d_taps`` for either after space to depth,
    ``fgc``, ``bgc``), and ``row_tiles`` each ``pe_conv_grad`` kernel
    call's row tiling as ``(H, th, tiles)`` (its last tile is ragged where
    ``tiles * th > H``); they are not part of :meth:`snapshot`, which
    covers only the whole-model pass counters."""

    __slots__ = ("forwards", "backwards", "probes", "fused", "conv_impls",
                 "row_tiles")

    def __init__(self):
        self.reset()

    def reset(self):
        self.forwards = 0
        self.backwards = 0
        self.probes = 0
        self.fused = 0
        self.conv_impls = collections.Counter()
        self.row_tiles = collections.Counter()

    def snapshot(self) -> dict:
        return {"forwards": self.forwards, "backwards": self.backwards,
                "probes": self.probes}


STATS = PipelineStats()

# ---------------------------------------------------------------------------
# Layer metadata


@dataclasses.dataclass
class LayerMeta:
    """Static description of one tapped layer.

    Attributes:
      kind: "dense" | "embed" | "scale" | "conv" | "local_vjp".
      path: pytree key path of this layer's param dict inside model params.
      param_key: key of the weight inside the layer param dict.
      bias_key: key of the bias (or None).
      w_transposed: "dense" only — weight stored (out, in), used as x @ W.T.
      segmented: captures carry explicit example ids ("seg") instead of a
        leading batch axis (MoE expert layers operate on dispatched slots).
      scanned: number of leading stacked-layer axes on the captures (0 for
        unscanned layers; nested scans add one each).
      shared: parameter is shared across scan steps / call sites (path is
        absolute from the params root; contributions must be *summed over
        applications before* taking norms — dense kinds realize this by
        folding the stacked axes into the sequence axis).
      static: extra static configuration (conv strides, n_examples, ...).
      fn: for "local_vjp": pure ``fn(param_subtree, *inputs) -> y``.
    """

    kind: str
    path: tuple
    param_key: str = "w"
    bias_key: str | None = None
    w_transposed: bool = False
    segmented: bool = False
    scanned: int = 0
    shared: bool = False
    static: dict = dataclasses.field(default_factory=dict)
    fn: Callable | None = None


def _parse_name(name: str) -> tuple[tuple, bool]:
    shared = name.startswith("~")
    return tuple(name.lstrip("~").split("/")), shared


class Tapper:
    """Records captures / applies taps while tracing a model.

    Modes:
      * ``"none"``    — plain forward; taps/captures untouched.
      * ``"probe"``   — record tap output shapes (abstract; use only under
                        ``jax.eval_shape``) plus captures.
      * ``"capture"`` — apply taps (if provided) and record captures.
    """

    def __init__(self, taps=None, mode: str = "none", metas: dict | None = None):
        self.taps = taps
        self.mode = mode
        self.captures: dict = {}
        self.metas: dict[str, LayerMeta] = metas if metas is not None else {}

    # -- core -------------------------------------------------------------
    def tap(self, name: str, y, captures: dict, meta: LayerMeta):
        if self.mode == "none":
            return y
        self.metas.setdefault(name, meta)
        if self.taps is not None and name in self.taps:
            y = y + self.taps[name].astype(y.dtype)
        rec = dict(captures)
        if self.mode == "probe":
            rec[TAP_KEY] = y
        self.captures[name] = rec
        return y

    def active(self) -> bool:
        return self.mode != "none"

    # -- layer helpers ----------------------------------------------------
    def dense(self, name: str, x, w, b=None, *, w_transposed: bool = False,
              param_key: str = "w"):
        """Tapped dense layer ``y = x @ W (+ b)``."""
        y = jnp.matmul(x, w.T if w_transposed else w)
        if b is not None:
            y = y + b
        path, shared = _parse_name(name)
        meta = LayerMeta("dense", path, param_key=param_key,
                         bias_key="b" if b is not None else None,
                         w_transposed=w_transposed, shared=shared)
        return self.tap(name, y, {"x": x}, meta)

    def dense_segmented(self, name: str, x, w, seg, b=None, *,
                        n_examples: int, stacked_axes: int = 1):
        """Dense over dispatched slots: x (*stack, S, Din) with example ids
        seg (*stack, S) and per-group weights w (*stack, Din, Dout) — e.g.
        MoE experts with stack = (E,).  ``stacked_axes`` counts the leading
        group axes (scan over layers adds more automatically)."""
        y = jnp.matmul(x, w)
        if b is not None:
            y = y + b
        path, shared = _parse_name(name)
        meta = LayerMeta("dense", path, bias_key="b" if b is not None else None,
                         segmented=True, shared=shared, scanned=stacked_axes,
                         static={"n_examples": n_examples})
        return self.tap(name, y, {"x": x, "seg": seg}, meta)

    def embed(self, name: str, table, ids):
        y = table[ids]
        path, shared = _parse_name(name)
        meta = LayerMeta("embed", path, param_key="emb", shared=shared)
        return self.tap(name, y, {"ids": ids}, meta)

    def scale(self, name: str, x, g, b=None):
        """Tapped elementwise affine (RMSNorm/LayerNorm): y = x*g (+ b)."""
        y = x * g
        if b is not None:
            y = y + b
        path, shared = _parse_name(name)
        meta = LayerMeta("scale", path, param_key="g",
                         bias_key="b" if b is not None else None, shared=shared)
        return self.tap(name, y, {"x": x}, meta)

    def conv(self, name: str, x, w, b=None, *, stride=1, dilation=1,
             padding=0, groups=1):
        """Tapped N-D convolution, NC(spatial) layout, weight (D, C/g, *K)."""
        from repro.models.convops import conv_forward  # avoid import cycle
        y = conv_forward(x, w, stride=stride, dilation=dilation,
                         padding=padding, groups=groups)
        if b is not None:
            y = y + b.reshape((1, -1) + (1,) * (y.ndim - 2))
        path, shared = _parse_name(name)
        meta = LayerMeta(
            "conv", path, bias_key="b" if b is not None else None, shared=shared,
            static={"stride": stride, "dilation": dilation, "padding": padding,
                    "groups": groups, "kernel_shape": tuple(w.shape)})
        return self.tap(name, y, {"x": x}, meta)

    def local_vjp(self, name: str, fn: Callable, params_sub, *inputs):
        """Tapped generic layer: per-example grads via layer-local VJP under
        vmap.  ``fn(params_sub, *inputs) -> y`` pure; inputs have leading B."""
        y = fn(params_sub, *inputs)
        path, shared = _parse_name(name)
        meta = LayerMeta("local_vjp", path, fn=fn, shared=shared)
        return self.tap(name, y, {"inputs": tuple(inputs)}, meta)


# ---------------------------------------------------------------------------
# Scan integration


def scan_with_taps(tp: Tapper, name: str, body_fn, carry, xs_params,
                   *, xs_extra=None, length=None, remat: bool = False,
                   shared_params=None, unroll: int = 1):
    """``lax.scan`` over stacked layers, threading taps and captures.

    ``body_fn(sub_tp, carry, params_l, extra_l[, shared_params]) -> carry``.
    ``xs_params`` is the stacked (leading L) parameter pytree;
    ``shared_params`` (optional) is an unstacked subtree passed to every
    step — taps against it must use the ``"~"`` absolute-name convention.
    """
    prefix = name + "/"
    taps_l = None
    if tp.taps is not None:
        sub = {k[len(prefix):]: v for k, v in tp.taps.items()
               if k.startswith(prefix)}
        taps_l = sub if sub else None
    sub_metas: dict[str, LayerMeta] = {}

    def body(c, xs):
        p_l, t_l, e_l = xs
        stp = Tapper(t_l, tp.mode, metas=sub_metas)
        if shared_params is None:
            c2 = body_fn(stp, c, p_l, e_l)
        else:
            c2 = body_fn(stp, c, p_l, e_l, shared_params)
        return c2, stp.captures

    if remat:
        body = jax.checkpoint(body, prevent_cse=False)

    carry, ys = lax.scan(body, carry, (xs_params, taps_l, xs_extra),
                         length=length, unroll=unroll)

    if tp.active():
        for sub_name, cap in ys.items():
            meta = sub_metas[sub_name]
            new_path = meta.path if meta.shared else tuple(name.split("/")) + meta.path
            tp.metas.setdefault(
                prefix + sub_name,
                dataclasses.replace(meta, path=new_path,
                                    scanned=meta.scanned + 1))
            tp.captures[prefix + sub_name] = cap
    return carry


# ---------------------------------------------------------------------------
# Probe and the capture backward pass


def probe(apply_fn, params, batch, *, return_captures: bool = False):
    """Shape-only trace.  Returns (make_taps, metas, tap_shapes) — with
    ``return_captures`` also the per-layer capture shape dicts (tap entry
    stripped), which the execution planner consumes."""
    STATS.probes += 1
    metas: dict[str, LayerMeta] = {}

    def f(p, b):
        tp = Tapper(None, "probe", metas=metas)
        losses = apply_fn(p, b, tp)
        return losses, tp.captures

    _, captures_shape = jax.eval_shape(f, params, batch)

    tap_shapes = {
        n: jax.ShapeDtypeStruct(c[TAP_KEY].shape, c[TAP_KEY].dtype)
        for n, c in captures_shape.items() if TAP_KEY in c
    }

    def make_taps():
        return {n: jnp.zeros(s.shape, s.dtype) for n, s in tap_shapes.items()}

    if return_captures:
        cap_shapes = {n: {k: v for k, v in c.items() if k != TAP_KEY}
                      for n, c in captures_shape.items()}
        return make_taps, metas, tap_shapes, cap_shapes
    return make_taps, metas, tap_shapes


def capture_backward(apply_fn, params, batch, taps, *,
                     with_metas: bool = False):
    """One backward pass → (per-example losses, captures, tap cotangents).

    ``with_metas`` additionally returns the :class:`LayerMeta` dict recorded
    while tracing — the *live* metadata, including ``fn`` references that a
    deserialized :class:`~repro.core.costmodel.ExecPlan` cannot carry."""
    STATS.forwards += 1
    STATS.backwards += 1
    metas: dict[str, LayerMeta] = {}

    def loss_from_taps(t):
        tp = Tapper(t, "capture", metas=metas)
        losses = apply_fn(params, batch, tp)
        return jnp.sum(losses), (losses, tp.captures)

    with jax.named_scope("dp.capture"):
        (_, (losses, caps)), dtaps = jax.value_and_grad(
            loss_from_taps, has_aux=True)(taps)
    if with_metas:
        return losses, caps, dtaps, metas
    return losses, caps, dtaps


# ---------------------------------------------------------------------------
# Pytree path helpers


def get_subtree(tree, path: tuple):
    for k in path:
        tree = tree[k]
    return tree


def set_subtree(tree: dict, path: tuple, value):
    """Functionally set a nested dict entry, creating intermediate dicts."""
    if len(path) == 1:
        out = dict(tree)
        out[path[0]] = value
        return out
    out = dict(tree)
    out[path[0]] = set_subtree(tree.get(path[0], {}), path[1:], value)
    return out
