"""Per-layer execution planner for the DP-SGD pipeline.

The paper's empirical finding is that which per-example-gradient strategy
wins depends on layer geometry (depth, width, batch, kernel size).  This
module turns that observation into an analytic *per-layer* plan: given the
tapped layers' :class:`~repro.core.tapper.LayerMeta` and capture/cotangent
shapes (from a single shape-only probe), it chooses

  norm phase (per layer)
    * ``gram``   — Gram-trick ghost norm, no per-example gradient
                   materialization (dense: FLOPs ≈ 2·B·T²·(Din+Dout);
                   conv via im2col: 2·B·T²·(C·K/g + D/g)·g);
    * ``stream`` / ``pe`` — materialize per-example grads then reduce
                   (dense: ≈ 4·B·T·Din·Dout; conv: ≈ 4·B·T·(C·K/g)·(D/g)·g),
                   bounded by a peak-memory budget;
    * ``rank1``  — no sequence axis: ‖g_b‖² = ‖x_b‖²·‖δy_b‖² exactly;
    * ``segsum`` / ``gram`` for embedding gathers.

  sum phase (per parameter group)
    * ``stash``    — the norm already materialized per-example grads;
                     keep them and form Σ_b w_b·g_b by a (B,)-weighted
                     reduction (zero recompute);
    * ``contrib``  — weighted per-layer contraction from the captures
                     (the book-keeping path);
    * ``backward`` — take this group's gradient from one shared weighted
                     backward pass; chosen only when the contraction
                     FLOPs exceed the layer's share of a backward by more
                     than the backward's fixed cost (forward recompute +
                     input-cotangent chain), amortized over all such
                     groups.

The strategy the plan executes is one of :data:`PLANNED_STRATEGIES`:
``auto`` makes every choice above; ``bk`` stashes nothing and contracts
every group; ``ghost`` stashes nothing and takes every group's sum from
the one weighted backward.  Under ``ghost`` and ``bk`` a layer keeps the
norm the caller fixed, else the best norm that stashes nothing.

Plans are cached on (model identity, batch/param shapes, knobs): steady
state training re-plans nothing and never re-probes — see
:func:`get_plan`.  Defaults target TPU v5e; the memory budget guards HBM
blow-ups on the materializing paths (the Gram paths are chunk-bounded).

Mesh-aware planning
-------------------
When a device mesh is supplied (a ``jax.sharding.Mesh``, a
``"data:8,model:2"`` spec string, or an axes mapping — see
:func:`mesh_axes`), every per-layer estimate becomes *per device*: the
batch-linear FLOPs and scratch shrink by the data-parallel degree (the
memory budget is per-device HBM), and each candidate realization is
additionally charged the collective traffic it induces, following the
communication patterns of distributed DP-SGD (Bu et al. 2022):

  * non-materializing norms (gram/ghost/segsum/rank1) all-reduce the
    per-example *scalar* norms — ``B·4`` bytes per layer;
  * materializing (stash) norms put per-example gradients on the
    gradient-sync path — the per-device stash crosses the ring;
  * every group pays its parameter-sized grad-sync all-reduce, and a
    shared weighted backward pays that psum a second time.

Bytes convert to FLOP-equivalents at :data:`COLLECTIVE_FLOPS_PER_BYTE`,
so plan selection can flip per layer under a mesh (e.g. a mid-network
conv whose materializing norm wins on FLOPs loses once its per-example
grads are charged ring traffic).  The mesh shape is folded into the plan
fingerprint and serialized payload: a plan loaded on a different
topology fails loudly (:func:`check_plan_matches`) instead of executing
a stale layout.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import math
import pathlib
from collections import OrderedDict
from fnmatch import fnmatchcase
from typing import Any, Callable, Mapping

import jax
import jax.numpy as jnp

from repro.core.tapper import LayerMeta, get_subtree, probe
from repro.launch.mesh import DATA_AXIS_NAMES

GRAM_CHUNK = 1024
# The strategies that execute an ExecPlan; naive, multi and crb
# materialize every per-example gradient instead (repro.core.strategies).
PLANNED_STRATEGIES = ("ghost", "bk", "auto")
STREAM_MEM_BUDGET = 2 << 30  # bytes of per-example-grad scratch we tolerate
BYTES = 4
# A weighted second backward costs ~2x the forward on top of the wgrad
# contractions it shares with `contrib`; expressed as a multiple of the
# total per-layer wgrad FLOPs (forward ≈ Σ wgrad, dx-chain ≈ Σ wgrad).
BACKWARD_FIXED_FACTOR = 2.0

# --- BEGIN ANALYTIC FALLBACK -------------------------------------------
# The documented fallback table: the ONLY place analytic bandwidth /
# FLOP-rate constants live.  Planning uses resolve_cost_constants(),
# which prefers a measured Calibration (repro.calibrate) for the live
# (hardware, mesh) and falls back to these values when none is
# registered.  CI greps that no magic `*_PER_BYTE = <digits>` constant
# exists outside this block.
#
#   collective_flops_per_byte — interconnect cost of one collective byte
#     in FLOP-equivalents.  TPU v5e: ~197 TFLOP/s bf16 against ~400 GB/s
#     aggregate ICI per chip ≈ 500 FLOPs/byte on the wire; DCN-attached
#     data parallelism is far worse.  BENCH_strategies.json shows this
#     constant can be catastrophically wrong (alexnet@data:8) — which is
#     exactly why measured calibration exists.
#   hbm_flops_per_byte — HBM cost of one byte in FLOP-equivalents (TPU
#     v5e: ~197 TFLOP/s bf16 against ~819 GB/s HBM ≈ 240; kept
#     conservative).  Credits the fused norm+contrib realizations under
#     stale-coefficient clipping: the Gram tiles and the contribution
#     accumulator share one HBM read of the captures.
#   flops_per_second — nominal device throughput used only to convert
#     FLOP-equivalents into predicted seconds when no calibration is
#     active (the mispredict loop needs a time unit).
ANALYTIC_FALLBACK = {
    "collective_flops_per_byte": 512.0,
    "hbm_flops_per_byte": 128.0,
    "flops_per_second": 197.0e12,
}
# --- END ANALYTIC FALLBACK ---------------------------------------------

# Module-level aliases kept for callers/tests that reference the analytic
# values by their historical names.
COLLECTIVE_FLOPS_PER_BYTE = ANALYTIC_FALLBACK["collective_flops_per_byte"]
HBM_FLOPS_PER_BYTE = ANALYTIC_FALLBACK["hbm_flops_per_byte"]


# ---------------------------------------------------------------------------
# Cost constants: calibrated lookups with the analytic table as fallback.
# Every cost term below prices through a CostConstants instance; the only
# question is whether it came from a measured Calibration or from
# ANALYTIC_FALLBACK.


@dataclasses.dataclass(frozen=True)
class CostConstants:
    """The rates one planning pass prices against, plus provenance.
    ``calibration`` is the Calibration digest ("" when analytic) — it is
    folded into plan fingerprints so plans built under different measured
    constants fail safe exactly like plans built from different code.

    ``collective_flops_per_byte_by_axis`` holds the per-mesh-axis wire
    prices (a hashable ``(("data", p), ("model", p))`` tuple) when the
    calibration measured them; :meth:`coll_price` is the per-axis lookup
    every collective cost term goes through, with the scalar
    ``collective_flops_per_byte`` as the fallback for axes that were
    never measured (and for legacy un-axed pricing)."""

    collective_flops_per_byte: float
    hbm_flops_per_byte: float
    flops_per_second: float
    source: str = "analytic"
    calibration: str = ""
    collective_flops_per_byte_by_axis: tuple = ()

    def coll_price(self, axis: str) -> float:
        """Wire price (FLOP-equivalents per byte) for traffic crossing
        ``axis`` — the measured per-axis rate when available, else the
        scalar constant."""
        for name, price in self.collective_flops_per_byte_by_axis:
            if name == axis:
                return price
        return self.collective_flops_per_byte


ANALYTIC_CONSTANTS = CostConstants(
    collective_flops_per_byte=ANALYTIC_FALLBACK["collective_flops_per_byte"],
    hbm_flops_per_byte=ANALYTIC_FALLBACK["hbm_flops_per_byte"],
    flops_per_second=ANALYTIC_FALLBACK["flops_per_second"])


def _resolve_calibration(calibration, mesh):
    """An explicit Calibration wins; ``None`` consults the registry for
    (live hardware, mesh).  Imported lazily — repro.calibrate imports
    this module."""
    if calibration is not None:
        return calibration
    try:
        from repro.calibrate import table as _ct
    except ImportError:      # pragma: no cover - calibrate always ships
        return None
    return _ct.lookup(mesh)


def resolve_cost_constants(calibration=None, mesh=None) -> CostConstants:
    """The :class:`CostConstants` a planning pass for ``mesh`` should
    price against: the given (or registered) calibration's measured
    rates, or :data:`ANALYTIC_CONSTANTS`.  A calibration with no
    collective measurements (e.g. measured off-mesh) keeps the analytic
    wire price — it has nothing better to say about it."""
    calib = _resolve_calibration(calibration, mesh)
    if calib is None:
        return ANALYTIC_CONSTANTS
    if calib.collective_bytes_per_second:
        # Price every measured axis explicitly — the scalar is the
        # slowest axis (max price), kept only as the fallback for axes
        # without a measurement.  Never the axis-less accessor here: that
        # path is the legacy slowest-axis mispricing and warns.
        by_axis = tuple(
            (axis, calib.collective_flops_per_byte(axis))
            for axis in sorted(calib.collective_bytes_per_second))
        coll = max(price for _, price in by_axis)
    else:
        by_axis = ()
        coll = ANALYTIC_FALLBACK["collective_flops_per_byte"]
    return CostConstants(
        collective_flops_per_byte=coll,
        hbm_flops_per_byte=calib.hbm_flops_per_byte(),
        flops_per_second=calib.flops_per_second,
        source=calib.source, calibration=calib.digest(),
        collective_flops_per_byte_by_axis=by_axis)

# contrib for a local_vjp layer replays the layer's VJP once *per
# example* under vmap — for scan-based layers (SSM recurrences) the
# vmapped per-example re-trace lowers far worse than the batched
# backward's single pass, so its contraction is charged a premium over
# the layer's wgrad share.  This is what can tip a local_vjp-dominated
# model into the shared weighted backward.
LOCAL_VJP_CONTRIB_PENALTY = 4.0
PLAN_CACHE_SIZE = 16


# ---------------------------------------------------------------------------
# Mesh normalization: every planner entry point takes ``mesh`` as a
# jax.sharding.Mesh, a "data:8,model:2" spec string, an axes mapping, or
# an (("data", 8), ...) tuple — all normalized to the tuple form, which
# is hashable (cache keys), JSON-able (plan payloads), and fingerprintable.


def _drop_unit_axes(axes: tuple) -> tuple:
    """Size-1 axes are topology no-ops: ``(("data", 8), ("model", 1))``
    executes identically to ``(("data", 8),)``, so they are normalized
    out — otherwise stored plans keyed on one spelling fail safe
    spuriously against the other (`check_plan_matches` compares the
    normalized tuples)."""
    return tuple((n, s) for n, s in axes if int(s) != 1)


def mesh_axes(mesh) -> tuple:
    """Normalize a mesh description to ``(("data", 8), ("model", 2))``.
    Size-1 axes are dropped (see :func:`_drop_unit_axes`)."""
    if mesh is None:
        return ()
    if isinstance(mesh, str):
        out = []
        for part in mesh.split(","):
            part = part.strip()
            if not part:
                continue
            name, sep, size = part.partition(":")
            if not sep or not size.strip().isdigit():
                raise ValueError(
                    f"bad mesh spec {mesh!r}; expected 'data:8' or "
                    f"'data:4,model:2'")
            out.append((name.strip(), int(size)))
        return _drop_unit_axes(tuple(out))
    if isinstance(mesh, Mapping):
        return _drop_unit_axes(
            tuple((str(k), int(v)) for k, v in mesh.items()))
    shape = getattr(mesh, "shape", None)
    if isinstance(shape, Mapping):        # jax.sharding.Mesh
        return _drop_unit_axes(
            tuple((str(k), int(v)) for k, v in shape.items()))
    return _drop_unit_axes(tuple((str(k), int(v)) for k, v in mesh))


def mesh_data_size(axes: tuple) -> int:
    d = 1
    for name, size in axes:
        if name in DATA_AXIS_NAMES:
            d *= int(size)
    return d


def mesh_data_axes(axes: tuple) -> tuple:
    """The data-parallel (batch-sharded) axes of a normalized mesh."""
    return tuple((n, s) for n, s in axes if n in DATA_AXIS_NAMES)


def mesh_model_axes(axes: tuple) -> tuple:
    """The model-parallel (tensor-sharded) axes of a normalized mesh."""
    return tuple((n, s) for n, s in axes if n not in DATA_AXIS_NAMES)


def mesh_model_size(axes: tuple) -> int:
    m = 1
    for _, size in mesh_model_axes(axes):
        m *= int(size)
    return m


def format_mesh(axes: tuple) -> str:
    return ("x".join(f"{n}={s}" for n, s in axes)) if axes else "(no mesh)"


def _ring(d: int) -> float:
    """Per-device bytes-on-the-wire multiplier of a ring all-reduce."""
    return 2.0 * (d - 1) / d if d > 1 else 0.0


# ---------------------------------------------------------------------------
# Scalar cost models (kept as the stable, unit-tested crossover formulas)


def dense_norm_method(T: int, Di: int, Do: int, B: int,
                      mem_budget: int = STREAM_MEM_BUDGET) -> str:
    if T == 1:
        return "rank1"
    gram_flops = 2 * T * T * (Di + Do)
    stream_flops = 4 * T * Di * Do
    stream_mem = B * Di * Do * BYTES
    if stream_flops < gram_flops and stream_mem <= mem_budget:
        return "stream"
    return "gram"


def seg_norm_method(S: int, Di: int, Do: int, B: int, G: int,
                    mem_budget: int = STREAM_MEM_BUDGET) -> str:
    """MoE expert slots: gram is O(G·S²·(Di+Do+B)), stream is
    O(G·B·Di·Do) FLOPs with (B·Di·Do) scratch per expert-group step."""
    gram_flops = G * S * S * (Di + Do + B)
    stream_flops = G * B * Di * Do
    stream_mem = B * Di * Do * BYTES
    if stream_flops < gram_flops and stream_mem <= mem_budget:
        return "stream"
    return "gram"


EMBED_PE_BUDGET = 32 << 20  # materialize embed pe grads below this


def embed_norm_method(T: int, D: int, B: int | None = None,
                      vocab: int | None = None,
                      pe_budget: int = EMBED_PE_BUDGET) -> str:
    """segsum is O(T·logT + T·D); the same-token-masked Gram is O(T²·D);
    materializing the (B, V, D) per-example grad (``pe``) costs O(B·V·D)
    but its sort-free scatter beats segsum's lane-serial argsort whenever
    the table is small — and the materialized grads make the sum phase
    free (stash).  ``pe`` is picked only under a hard memory bound."""
    if B is not None and vocab is not None \
            and B * vocab * D * BYTES <= pe_budget:
        return "pe"
    return "gram" if T <= 32 else "segsum"


# ---------------------------------------------------------------------------
# Plan structures


@dataclasses.dataclass(frozen=True)
class LayerPlan:
    """Per-tap decision + cost estimates.

    All estimates are *per device*: with no mesh that is the whole batch;
    under a mesh the batch-linear FLOPs and scratch are for this device's
    batch shard, and ``coll_bytes`` is this device's share of the
    collective traffic the chosen realization induces per step."""

    name: str
    kind: str
    norm_method: str          # gram|stream|rank1|pallas|pe|segsum|...
    stash: bool               # norm phase materializes per-example grads
    norm_flops: float
    contrib_flops: float
    wgrad_flops: float        # this layer's share of a weighted backward
    stash_bytes: float = 0.0  # size of the (B, *param) grads if stashed
    fallback_norm: str = ""   # best no-stash method (cumulative demotion)
    param_bytes: float = 0.0  # parameter bytes (grad-sync unit, per shard)
    coll_bytes: float = 0.0   # predicted collective bytes per step
    ex_per_dev: float = 0.0   # examples on one device's batch shard
    fused: bool = False       # stale mode: single-pass gram_norm_fused
    model_shards: int = 1     # tensor-parallel degree this layer splits over
    coll_bytes_by_axis: tuple = ()  # (("data", bytes), ...) per mesh axis


@dataclasses.dataclass(frozen=True)
class GroupPlan:
    """One parameter (pytree path); >1 member means shared/tied taps."""

    path: tuple
    members: tuple                 # tap names
    norm_mode: str                 # single | tied | group_pe
    sum_method: str                # stash | contrib | backward


PLAN_FORMAT_VERSION = 7   # v7: block-level "attn" realization (ghost/pe)

_META_FIELDS = ("kind", "path", "param_key", "bias_key", "w_transposed",
                "segmented", "scanned", "shared", "static")


def _retuple(x):
    """JSON arrays back to tuples (paths, kernel shapes, strides...)."""
    if isinstance(x, list):
        return tuple(_retuple(v) for v in x)
    if isinstance(x, dict):
        return {k: _retuple(v) for k, v in x.items()}
    return x


def _jsonable(x):
    if isinstance(x, tuple):
        return [_jsonable(v) for v in x]
    if isinstance(x, dict):
        return {k: _jsonable(v) for k, v in x.items()}
    return x


def _make_taps_from(tap_shapes: dict) -> Callable:
    def make_taps():
        return {n: jnp.zeros(s.shape, s.dtype) for n, s in tap_shapes.items()}
    return make_taps


@dataclasses.dataclass(frozen=True, eq=False)
class ExecPlan:
    """The per-layer execution plan — a first-class, frozen value.

    Inspect with :meth:`explain` (per-layer table of chosen norm/sum
    realizations with predicted FLOPs/bytes), serialize with
    :meth:`to_json` / :meth:`from_json` for cross-process caching keyed on
    :attr:`fingerprint` (model + batch/param shapes + planner knobs).  A
    deserialized plan executes without re-probing: tap zeros are rebuilt
    from :attr:`tap_shapes` and layer metadata is re-validated against the
    live capture trace (so a stale plan fails loudly, not wrongly).
    """

    groups: tuple
    layers: dict                   # name -> LayerPlan
    metas: dict                    # name -> LayerMeta
    make_taps: Callable
    needs_backward: bool
    total_norm_flops: float
    total_contrib_flops: float
    tap_shapes: dict = dataclasses.field(default_factory=dict)
    capture_bytes: float = 0.0     # captures + tap cotangents, per device
    fingerprint: str = ""
    mesh: tuple = ()               # (("data", 8), ...) this plan targets
    batch_sig: tuple = ()          # batch shape signature the plan was built on
    total_coll_bytes: float = 0.0  # per-device collective bytes per step
    total_coll_bytes_by_axis: tuple = ()  # (("data", bytes), ...) breakdown
    clip_mode: str = "flat"        # flat | per_layer | stale (coefficient flow)
    calibration: str = ""          # Calibration digest priced under ("" analytic)
    _anchor: Any = None            # pins apply_fn identity while cached

    def describe(self) -> str:
        lines = []
        for g in self.groups:
            for n in g.members:
                lp = self.layers[n]
                lines.append(f"{n}: kind={lp.kind} norm={lp.norm_method} "
                             f"sum={g.sum_method}")
        return "\n".join(lines)

    # -- inspection --------------------------------------------------------

    def sum_methods(self) -> dict:
        return {n: g.sum_method for g in self.groups for n in g.members}

    def peak_stash_bytes(self) -> float:
        """Stashes coexist from the norm phase to the sum phase; a group's
        members share one parameter, so it stashes one (B, *param) tree."""
        return sum(max(self.layers[n].stash_bytes for n in g.members)
                   for g in self.groups if g.sum_method == "stash")

    def explain(self) -> str:
        """Per-layer table of the chosen realizations and predicted costs
        (per device; the ``coll MB`` column is the predicted collective
        traffic the realization induces on the plan's mesh)."""
        sums = self.sum_methods()
        header = (f"{'layer':<28} {'kind':<10} {'norm':<8} {'sum':<9} "
                  f"{'norm MF':>9} {'sum MF':>9} {'stash MB':>9} "
                  f"{'coll MB':>9}")
        lines = [header, "-" * len(header)]
        for n, lp in self.layers.items():
            stash_mb = lp.stash_bytes / 2**20 if lp.stash else 0.0
            sum_m = "fused" if lp.fused else sums.get(n, "?")
            lines.append(
                f"{n:<28} {lp.kind:<10} {lp.norm_method:<8} "
                f"{sum_m:<9} {lp.norm_flops / 1e6:>9.2f} "
                f"{lp.contrib_flops / 1e6:>9.2f} {stash_mb:>9.2f} "
                f"{lp.coll_bytes / 2**20:>9.2f}")
        passes = ("2 fwd + 2 bwd (shared weighted backward)"
                  if self.needs_backward else "1 fwd + 1 bwd")
        n_fused = sum(lp.fused for lp in self.layers.values())
        lines.append("-" * len(header))
        lines.append(
            f"steady-state passes: {passes}; total norm "
            f"{self.total_norm_flops / 1e6:.2f} MF, contrib "
            f"{self.total_contrib_flops / 1e6:.2f} MF; captures "
            f"{self.capture_bytes / 2**20:.2f} MB, peak stash "
            f"{self.peak_stash_bytes() / 2**20:.2f} MB")
        lines.append(
            f"clipping mode: {self.clip_mode}"
            + (f" ({n_fused} fused single-pass norm+contrib layer"
               f"{'s' if n_fused != 1 else ''})" if n_fused else ""))
        per_axis = ("; per axis: " + ", ".join(
            f"{a}={b / 2**20:.2f} MB"
            for a, b in self.total_coll_bytes_by_axis)
            if self.total_coll_bytes_by_axis else "")
        lines.append(
            f"mesh: {format_mesh(self.mesh)}; predicted collectives "
            f"{self.total_coll_bytes / 2**20:.2f} MB/step/device"
            + per_axis)
        lines.append(
            f"cost constants: measured calibration {self.calibration}"
            if self.calibration else
            "cost constants: analytic fallback (no calibration)")
        if self.fingerprint:
            lines.append(f"fingerprint: {self.fingerprint}")
        return "\n".join(lines)

    # -- serialization -----------------------------------------------------

    def to_payload(self) -> dict:
        metas = {n: {f: _jsonable(getattr(m, f)) for f in _META_FIELDS}
                 for n, m in self.metas.items()}
        return {
            "format": PLAN_FORMAT_VERSION,
            "fingerprint": self.fingerprint,
            "mesh": _jsonable(self.mesh),
            "batch_sig": _jsonable(self.batch_sig),
            "clip_mode": self.clip_mode,
            "needs_backward": self.needs_backward,
            "total_norm_flops": self.total_norm_flops,
            "total_contrib_flops": self.total_contrib_flops,
            "total_coll_bytes": self.total_coll_bytes,
            "total_coll_bytes_by_axis":
                _jsonable(self.total_coll_bytes_by_axis),
            "calibration": self.calibration,
            "capture_bytes": self.capture_bytes,
            "layers": {n: _jsonable(dataclasses.asdict(lp))
                       for n, lp in self.layers.items()},
            "groups": [{"path": list(g.path), "members": list(g.members),
                        "norm_mode": g.norm_mode,
                        "sum_method": g.sum_method} for g in self.groups],
            "metas": metas,
            "tap_shapes": {n: {"shape": list(s.shape), "dtype": str(s.dtype)}
                           for n, s in self.tap_shapes.items()},
        }

    def to_json(self, **json_kw) -> str:
        return json.dumps(self.to_payload(), **json_kw)

    @classmethod
    def from_payload(cls, p: dict) -> "ExecPlan":
        if p.get("format") != PLAN_FORMAT_VERSION:
            raise ValueError(
                f"unsupported plan format {p.get('format')!r} "
                f"(this build reads {PLAN_FORMAT_VERSION})")
        layers = {
            n: LayerPlan(**{**d, "coll_bytes_by_axis":
                            _retuple(d.get("coll_bytes_by_axis", []))})
            for n, d in p["layers"].items()}
        groups = tuple(
            GroupPlan(tuple(g["path"]), tuple(g["members"]),
                      g["norm_mode"], g["sum_method"]) for g in p["groups"])
        metas = {}
        for n, d in p["metas"].items():
            metas[n] = LayerMeta(
                kind=d["kind"], path=tuple(d["path"]),
                param_key=d["param_key"], bias_key=d["bias_key"],
                w_transposed=d["w_transposed"], segmented=d["segmented"],
                scanned=d["scanned"], shared=d["shared"],
                static=_retuple(d["static"]))
        tap_shapes = {
            n: jax.ShapeDtypeStruct(tuple(s["shape"]), s["dtype"])
            for n, s in p["tap_shapes"].items()}
        return cls(groups=groups, layers=layers, metas=metas,
                   make_taps=_make_taps_from(tap_shapes),
                   needs_backward=p["needs_backward"],
                   total_norm_flops=p["total_norm_flops"],
                   total_contrib_flops=p["total_contrib_flops"],
                   tap_shapes=tap_shapes,
                   capture_bytes=p["capture_bytes"],
                   fingerprint=p["fingerprint"],
                   mesh=_retuple(p.get("mesh", [])),
                   batch_sig=_retuple(p.get("batch_sig", [])),
                   total_coll_bytes=p.get("total_coll_bytes", 0.0),
                   total_coll_bytes_by_axis=_retuple(
                       p.get("total_coll_bytes_by_axis", [])),
                   clip_mode=p.get("clip_mode", "flat"),
                   calibration=p.get("calibration", ""))

    @classmethod
    def from_json(cls, s: str) -> "ExecPlan":
        return cls.from_payload(json.loads(s))

    def __eq__(self, other) -> bool:
        """Semantic equality: the serialized payload (closures and live
        ``fn`` references excluded), so ``from_json(to_json(p)) == p``."""
        if not isinstance(other, ExecPlan):
            return NotImplemented
        return self.to_payload() == other.to_payload()


# ---------------------------------------------------------------------------
# Per-layer geometry + planning


def _prod(xs) -> int:
    return int(math.prod(int(x) for x in xs)) if xs else 1


def _tree_elems(tree) -> int:
    return sum(_prod(leaf.shape) for leaf in jax.tree.leaves(tree))


def _plan_layer(name: str, meta: LayerMeta, cap_sh: dict, dy_sh,
                *, norm_method: str, embed_method: str, conv_norm: str,
                mem_budget: int, vocab: int | None = None,
                params_sub=None, mesh: tuple = (),
                clip_mode: str = "flat",
                clip_fused: bool = True, conv_impl: str = "auto",
                cc: CostConstants = ANALYTIC_CONSTANTS) -> LayerPlan:
    """Costs for one tap.  Stacked (scanned) applications multiply the
    per-application cost; shared stacked dense/scale layers fold the stack
    into the sequence axis first (matching kinds.apply_kind semantics).

    The auto choice minimizes the *joint* norm + sum cost: a norm that
    materializes per-example grads makes the sum phase a free (B,)-weighted
    reduction over the stash, so ``stream``/``pe`` is charged once while
    ``gram``/``ghost`` is charged norm + contraction.

    Under a mesh all estimates are per device (batch-linear terms use the
    per-device batch shard; the memory budget is per-device HBM), and the
    candidates additionally pay their collective traffic in
    FLOP-equivalents: stash candidates put per-example grads on the wire,
    non-materializing norms all-reduce ``B`` scalars."""
    k = meta.scanned
    dy_shape = tuple(dy_sh.shape)
    stack = _prod(dy_shape[:k])
    app_dy = dy_shape[k:]
    d = mesh_data_size(mesh)
    ring = _ring(d)
    daxes = mesh_data_axes(mesh)
    maxes = mesh_model_axes(mesh)
    msize = mesh_model_size(mesh)

    def _shard(B: int) -> int:
        return max(1, -(-int(B) // d))

    def _data_wire(nbytes: float) -> float:
        # Bytes crossing the data-parallel ring(s), priced on the axis
        # they actually cross: a hierarchical all-reduce moves ring(s)
        # bytes per axis of size s, each at that axis's measured price.
        return sum(cc.coll_price(a) * nbytes * _ring(s) for a, s in daxes)

    def _model_wire(nbytes: float) -> float:
        # Bytes psum'd over the model (tensor-parallel) axes — the
        # partial-Gram / partial-norm reduction of tensor-sharded layers.
        return sum(cc.coll_price(a) * nbytes * _ring(s) for a, s in maxes)

    def _scal_cost(B: int, model_sharded: bool = False) -> float:
        # all-reduce of the per-example scalar norms: (B,) float32.
        # Per-layer clipping drops the *data*-axis reduction: a layer's
        # coefficient depends only on its own norm, which lives on the
        # shard holding the example.  A tensor-sharded layer still pays
        # the model-axis psum — its per-example norm is assembled from
        # partial Grams that live on every model shard.
        w = 0.0 if clip_mode == "per_layer" else _data_wire(B * BYTES)
        if model_sharded:
            w += _model_wire(B * BYTES)
        return w

    def _fused_credit(read_bytes: float, cand_flops: float) -> float:
        # Stale coefficients are known entering the pass, so the Gram
        # norm and the weighted contribution share one HBM read of the
        # captures (gram_norm_fused) instead of two passes.  The credit
        # is capped at a sliver of the candidate's own FLOPs so it
        # breaks near-ties toward fusing but can never flip a layer
        # whose materializing path holds a real compute advantage (the
        # CPU/ref realization has no HBM read to save, and even on TPU
        # the read saving is second-order next to a FLOP gap).
        if clip_mode == "stale" and clip_fused:
            return min(cc.hbm_flops_per_byte * read_bytes,
                       0.05 * cand_flops)
        return 0.0

    def _move_cost(stash_bytes: float) -> float:
        # per-device per-example grads crossing the grad-sync ring; a
        # tensor-sharded layer's stash is its local param slice, so the
        # caller passes the already-divided per-shard bytes
        return _data_wire(stash_bytes)

    if meta.kind == "dense" and meta.segmented:
        x_shape = tuple(cap_sh["x"].shape)[k:]
        S, Di, Do = x_shape[-2], x_shape[-1], app_dy[-1]
        G = _prod(x_shape[:-2]) * stack
        B = meta.static["n_examples"]
        Bl = _shard(B)
        # Expert-sharded MoE layers place G/msh experts per model shard.
        msh = msize if msize > 1 and G % msize == 0 else 1
        Gl = G // msh
        m = (norm_method if norm_method not in ("auto", "pallas")
             else seg_norm_method(S, Di, Do, Bl, Gl, mem_budget))
        nf = (Gl * S * S * (Di + Do + Bl) if m == "gram"
              else Gl * Bl * Di * Do)
        cf = 2.0 * Gl * S * Di * Do
        return LayerPlan(name, "seg_dense", m, False, nf, cf, cf,
                         stash_bytes=Bl * Gl * Di * Do * BYTES,
                         param_bytes=Gl * Di * Do * BYTES, ex_per_dev=Bl,
                         model_shards=msh)

    if meta.kind == "dense":
        x_shape = tuple(cap_sh["x"].shape)[k:]
        B, Di, Do = x_shape[0], x_shape[-1], app_dy[-1]
        Bl = _shard(B)
        T = _prod(x_shape[1:-1])
        mult = stack
        if meta.shared and k:
            T, mult = T * stack, 1        # folded into the sequence axis
        # Tensor sharding over the model axes partitions the output
        # width: each device contracts its local Do/msh slice (the input
        # activations stay replicated), the per-example norm is the
        # model-axis psum of the partial Grams, and the stash/param
        # footprint is the local slice.
        msh = msize if msize > 1 and Do % msize == 0 else 1
        Dol = Do // msh
        cf = 2.0 * Bl * T * Di * Dol * mult
        pbytes = Di * Dol * BYTES * mult
        # Stashing keeps (B, *stack, Di, Do/msh) alive until the sum
        # phase; the un-stashed stream norm reduces one stacked layer at
        # a time (kinds.apply_kind's sequential loop), so it only needs
        # one layer's scratch but pays the contraction again in phase 2.
        mem_stash = Bl * Di * Dol * BYTES * mult
        mem_layer = Bl * Di * Dol * BYTES
        stash = False
        fallback = norm_method
        if norm_method == "auto":
            if T == 1:
                m = fallback = "rank1"
            else:
                per_ex = Bl * mult
                gram_flops = (2.0 * T * T * (Di + Dol)
                              + 2.0 * T * Di * Dol) * per_ex
                gram_total = (gram_flops + _scal_cost(B, msh > 1)
                              - _fused_credit(
                                  T * (Di + Dol) * BYTES * per_ex,
                                  gram_flops))
                stream_stash = (4.0 * T * Di * Dol * per_ex
                                + _move_cost(mem_stash))
                stream_again = (4.0 * T * Di * Dol
                                + 2.0 * T * Di * Dol) * per_ex \
                    + _scal_cost(B, msh > 1)
                fallback = ("stream" if stream_again < gram_total
                            and mem_layer <= mem_budget else "gram")
                if stream_stash < gram_total and mem_stash <= mem_budget:
                    m, stash = "stream", True
                else:
                    m = fallback
        else:
            m = norm_method
            stash = m == "stream" and mem_stash <= mem_budget
        if m == "rank1" and T != 1:
            m = fallback = "gram"
        nf = {"gram": 2.0 * T * T * (Di + Dol),
              "pallas": 2.0 * T * T * (Di + Dol),
              "stream": 4.0 * T * Di * Dol,
              "rank1": 2.0 * T * (Di + Dol)}[m] * Bl * mult
        return LayerPlan(name, "dense", m, stash, nf, cf, cf,
                         stash_bytes=mem_stash, fallback_norm=fallback,
                         param_bytes=pbytes, ex_per_dev=Bl,
                         model_shards=msh)

    if meta.kind == "conv":
        st = meta.static
        x_shape = tuple(cap_sh["x"].shape)[k:]
        B, C = x_shape[0], x_shape[1]
        Bl = _shard(B)
        D = app_dy[1]
        T = _prod(app_dy[2:])
        K = _prod(st["kernel_shape"][2:])
        g = max(st.get("groups", 1), 1)
        F, Dg = (C // g) * K, D // g
        # The per-example gradient's own taps, those of the route it takes
        # (through space to depth, s·ceil(k/s) per axis cropped to k).
        from repro.models import convops
        route = convops.pe_conv_route(
            st["kernel_shape"][2:], C, stride=st.get("stride", 1),
            dilation=st.get("dilation", 1), padding=st.get("padding", 0),
            groups=g, impl=conv_impl)
        Fpe = (C // g) * convops.route_taps(route, st["kernel_shape"][2:],
                                            st.get("stride", 1))
        # Tensor sharding partitions the output channels: each model
        # shard owns Dg/msh filters per group, contracts its local patch
        # slice for the ghost norm, and psums the partial per-example
        # norms over the model axes.
        msh = msize if msize > 1 and Dg % msize == 0 else 1
        Dgl = Dg // msh
        cf = 2.0 * Bl * T * F * Dgl * g * stack
        pbytes = (D // msh) * (C // g) * K * BYTES * stack
        mem_stash = Bl * (D // msh) * (C // g) * K * BYTES * stack
        mem_layer = Bl * (D // msh) * (C // g) * K * BYTES
        stash = False
        fallback = conv_norm
        if conv_norm == "auto":
            per_ex = Bl * stack
            ghost_flops = (2.0 * T * T * (F + Dgl)
                           + 2.0 * T * F * Dgl) * g * per_ex
            ghost_total = (ghost_flops + _scal_cost(B, msh > 1)
                           - _fused_credit(
                               T * (F + Dgl) * g * BYTES * per_ex,
                               ghost_flops))
            pe_stash = (4.0 * T * Fpe * Dgl * g * per_ex
                        + _move_cost(mem_stash))
            pe_again = ((4.0 * T * Fpe * Dgl + 2.0 * T * F * Dgl) * g
                        * per_ex + _scal_cost(B, msh > 1))
            fallback = ("pe" if pe_again < ghost_total
                        and mem_layer <= mem_budget else "ghost")
            if pe_stash < ghost_total and mem_stash <= mem_budget:
                m, stash = "pe", True
            else:
                m = fallback
        else:
            m = conv_norm
            stash = m == "pe" and mem_stash <= mem_budget
        nf = (2.0 * Bl * T * T * (F + Dgl) * g if m == "ghost"
              else 4.0 * Bl * T * Fpe * Dgl * g) * stack
        return LayerPlan(name, "conv", m, stash, nf, cf, cf,
                         stash_bytes=mem_stash, fallback_norm=fallback,
                         param_bytes=pbytes, ex_per_dev=Bl,
                         model_shards=msh)

    if meta.kind == "embed":
        ids_shape = tuple(cap_sh["ids"].shape)[k:]
        B = ids_shape[0]
        Bl = _shard(B)
        T = _prod(ids_shape[1:])
        D = app_dy[-1]
        V = vocab or T
        # A vocab-sharded table keeps V/msh rows per model shard; the
        # same-token Gram and segsum norms see only locally-owned rows,
        # so their partial norms psum over the model axes.
        msh = msize if msize > 1 and V % msize == 0 else 1
        Vl = V // msh
        pbytes = Vl * D * BYTES * stack
        stash_bytes = Bl * Vl * D * BYTES * stack
        seg_f = (T * max(math.log2(max(T, 2)), 1.0) + 2.0 * T * D)
        costs = {"pe": Bl * (T * D + Vl * D) * stack
                 + _move_cost(stash_bytes),
                 "gram": 2.0 * Bl * T * T * D * stack
                 + _scal_cost(B, msh > 1),
                 "segsum": Bl * seg_f * stack + _scal_cost(B, msh > 1)}
        if embed_method != "auto":
            m = embed_method
        elif not mesh:
            # stack multiplies the stashed (B, V, D) scratch for the budget
            m = embed_norm_method(T, D, B * stack, vocab)
        else:
            # Mesh-aware: the stash's ring traffic competes with the
            # scalar all-reduce of the ghost realizations.
            m = min(costs, key=costs.get)
            if m == "pe" and stash_bytes > EMBED_PE_BUDGET:
                m = "gram" if T <= 32 else "segsum"
        nf = {"gram": 2.0 * Bl * T * T * D,
              "pe": Bl * (T * D + Vl * D),
              "segsum": Bl * seg_f}[m] * stack
        cf = 2.0 * Bl * T * D * stack
        fb = (m if m != "pe" else ("gram" if T <= 32 else "segsum"))
        return LayerPlan(name, "embed", m, m == "pe", nf, cf, cf,
                         stash_bytes=stash_bytes, fallback_norm=fb,
                         param_bytes=pbytes, ex_per_dev=Bl,
                         model_shards=msh)

    if meta.kind == "scale":
        B = app_dy[0] if app_dy else 1
        Bl = _shard(B)
        n = 2.0 * Bl * (_prod(app_dy) // max(B, 1)) * stack
        return LayerPlan(name, "scale", "pe", True, n, n, n,
                         stash_bytes=Bl * app_dy[-1] * BYTES * stack
                         if app_dy else 0.0,
                         param_bytes=(app_dy[-1] * BYTES * stack
                                      if app_dy else 0.0),
                         ex_per_dev=Bl)

    if meta.kind == "attn":
        # Whole attention block tapped as a unit (gqa/mla dp_attn): the
        # norm phase recomputes the block forward+backward once (the
        # layer-local tap-differentiation in kinds._attn_parts costs one
        # fwd + one bwd of the block, ≈ 3x the projection matmuls plus
        # the T² score work) and then realizes each projection's norm:
        # "ghost" runs the inner Gram contractions, "pe" materializes and
        # stashes per-projection per-example grads so the sum phase is a
        # free weighted reduction over the stash.
        x_shape = tuple(cap_sh["x"].shape)[k:]
        B = x_shape[0]
        Bl = _shard(B)
        T = _prod(x_shape[1:-1])
        proj = tuple(meta.static["proj_dims"])
        qk = meta.static.get("qk_flops", 0)
        per_ex = Bl * stack
        proj_flops = sum(2.0 * T * Di * Do for Di, Do in proj)
        recompute = 3.0 * (proj_flops + 4.0 * T * T * qk) * per_ex
        gram = sum(2.0 * T * T * (Di + Do) for Di, Do in proj) * per_ex
        outer = 2.0 * proj_flops * per_ex
        psize = sum(Di * Do for Di, Do in proj)
        mem_stash = Bl * psize * BYTES * stack
        pbytes = psize * BYTES * stack
        ghost_total = recompute + gram + _scal_cost(B)
        pe_stash = recompute + outer + _move_cost(mem_stash)
        m_req = norm_method if norm_method in ("ghost", "pe") else "auto"
        stash = False
        if m_req == "auto":
            if pe_stash < ghost_total and mem_stash <= mem_budget:
                m, stash = "pe", True
            else:
                m = "ghost"
        else:
            m = m_req
            stash = m == "pe" and mem_stash <= mem_budget
        nf = recompute + (outer if m == "pe" else gram)
        cf = recompute + proj_flops * per_ex
        return LayerPlan(name, "attn", m, stash, nf, cf,
                         proj_flops * per_ex,
                         stash_bytes=mem_stash, fallback_norm="ghost",
                         param_bytes=pbytes, ex_per_dev=Bl)

    # local_vjp: a layer-local VJP under vmap.  The norm phase
    # materializes per-example grads and stashes them when the (B, *param)
    # scratch fits the budget, making the sum free.  When the stash is
    # vetoed, the standalone contraction replays the per-example VJP —
    # charged LOCAL_VJP_CONTRIB_PENALTY over the batched backward's share
    # (vmap of a scan-based layer lowers far worse than one batched
    # backward) — which is what can tip the plan into the shared
    # weighted backward.
    B = app_dy[0] if app_dy else 1
    Bl = _shard(B)
    n = 2.0 * Bl * (_prod(app_dy) // max(B, 1)) * stack
    # params_sub at meta.path already carries the stacked axis in its leaf
    # shapes for scanned layers, so B * elems is the full stash size.
    psize = _tree_elems(params_sub) if params_sub is not None else 0
    stash_mem = Bl * psize * BYTES
    stash = psize == 0 or stash_mem <= mem_budget
    return LayerPlan(name, meta.kind, "pe", stash, n,
                     LOCAL_VJP_CONTRIB_PENALTY * n, n,
                     stash_bytes=stash_mem, param_bytes=psize * BYTES,
                     ex_per_dev=Bl)


def _vocab_of(meta: LayerMeta, params) -> int | None:
    if params is None:
        return meta.static.get("vocab")
    try:
        leaf = get_subtree(params, meta.path)[meta.param_key]
        return int(leaf.shape[-2])
    except (KeyError, TypeError, IndexError):
        return None


_OVERRIDE_METHODS = {
    "dense": {"auto", "gram", "stream", "rank1", "pallas"},
    "embed": {"auto", "segsum", "gram", "pe"},
    "conv": {"auto", "ghost", "pe", "pallas"},
    "attn": {"auto", "ghost", "pe"},
}


def normalize_overrides(overrides) -> tuple:
    """Per-layer overrides as an ordered, hashable tuple of (pattern,
    method) pairs.  Patterns are fnmatch globs over tap names (``"conv1"``,
    ``"blocks/*"``); the first match wins, in the order given (dict
    insertion order is preserved)."""
    if not overrides:
        return ()
    if isinstance(overrides, Mapping):
        overrides = overrides.items()
    return tuple((str(p), str(m)) for p, m in overrides)


def _override_for(name: str, kind: str, overrides: tuple) -> str | None:
    """First matching override for this layer.  Kinds with no override
    vocabulary (scale, local_vjp) ignore matches — a block-level glob like
    ``"blocks/*"`` inevitably sweeps up their taps — but a method that is
    wrong for an overridable kind is a hard error."""
    valid = _OVERRIDE_METHODS.get(kind)
    if valid is None:
        return None
    for pat, m in overrides:
        if fnmatchcase(name, pat):
            if m not in valid:
                raise ValueError(
                    f"per-layer override {pat!r}={m!r} invalid for {kind} "
                    f"layer {name!r}; choose from {sorted(valid)}")
            return m
    return None


def _nbytes(sds) -> float:
    return float(_prod(sds.shape)) * jnp.dtype(sds.dtype).itemsize


def plan_execution(metas: dict, cap_shapes: dict, tap_shapes: dict,
                   make_taps: Callable, params=None, *,
                   norm_method: str = "auto", embed_method: str = "auto",
                   conv_norm: str = "auto",
                   mem_budget: int = STREAM_MEM_BUDGET,
                   overrides=None, mesh=None, clip_mode: str = "flat",
                   clip_fused: bool = True, calibration=None,
                   conv_impl: str = "auto",
                   strategy: str = "auto") -> ExecPlan:
    """Build the per-layer plan from probed shapes, for one of
    :data:`PLANNED_STRATEGIES`.

    Fixed ``norm_method`` / ``embed_method`` / ``conv_norm`` override the
    analytic choice uniformly (the planner still fills in cost estimates);
    ``overrides`` pins individual layers by tap-name glob and wins over
    both.  ``mesh`` (anything :func:`mesh_axes` accepts) switches every
    estimate to per-device and charges candidates their collective bytes.

    ``clip_mode`` shapes the plan around the coefficient flow of the
    executing :class:`~repro.core.clipping.ClipPolicy`: ``per_layer``
    drops the cross-layer norm all-reduce from the collective model and
    never selects the shared weighted backward (one backward cannot
    realize per-layer weights); ``stale`` also drops the backward (the
    known coefficients make every contraction direct) and, with
    ``clip_fused``, credits and marks Gram-realized dense/conv layers
    for the fused single-pass ``gram_norm_fused`` norm+contrib.

    ``calibration`` (a :class:`repro.calibrate.Calibration`, or ``None``
    for the registered one) supplies measured cost constants; every
    price below goes through the resolved :class:`CostConstants`, with
    :data:`ANALYTIC_CONSTANTS` as the documented fallback.
    """
    if strategy not in PLANNED_STRATEGIES:
        raise ValueError(
            f"strategy {strategy!r} has no plan; planned strategies are "
            f"{PLANNED_STRATEGIES}")
    overrides = normalize_overrides(overrides)
    ms = mesh_axes(mesh)
    d = mesh_data_size(ms)
    cc = resolve_cost_constants(calibration, ms)
    layers: dict[str, LayerPlan] = {}
    by_path: dict[tuple, list] = {}
    for name, meta in metas.items():
        psub = None
        if params is not None and meta.kind == "local_vjp":
            try:
                psub = get_subtree(params, meta.path)
            except (KeyError, TypeError):
                psub = None
        ov = _override_for(name, meta.kind, overrides)
        lp = _plan_layer(
            name, meta, cap_shapes[name], tap_shapes[name],
            norm_method=ov or norm_method, embed_method=ov or embed_method,
            conv_norm=ov or conv_norm, mem_budget=mem_budget,
            vocab=_vocab_of(meta, params) if meta.kind == "embed" else None,
            params_sub=psub, mesh=ms, clip_mode=clip_mode,
            clip_fused=clip_fused, conv_impl=conv_impl, cc=cc)
        if strategy != "auto" and lp.stash:
            # ghost and bk stash nothing: the layer keeps the norm the
            # caller asked for, else takes its best norm without a stash.
            asked = ov or {"embed": embed_method,
                           "conv": conv_norm}.get(meta.kind, norm_method)
            lp = dataclasses.replace(
                lp, stash=False,
                norm_method=(lp.norm_method if asked == lp.norm_method
                             else lp.fallback_norm or lp.norm_method))
        layers[name] = lp
        by_path.setdefault(meta.path, []).append(name)

    total_wgrad = sum(lp.wgrad_flops for lp in layers.values())
    # A weighted backward pays the forward + dx chain (the fixed factor)
    # AND computes every parameter's wgrad — including those of groups
    # that keep their stash/contraction, whose share is pure waste.  So
    # switching the candidate set to the backward only pays off when the
    # contractions it replaces exceed fixed + total_wgrad.  Under a mesh
    # it also psums the whole gradient a second time — sized by *unique*
    # parameters (taps sharing a path sync one gradient, not one each).
    unique_pbytes = sum(max(layers[n].param_bytes for n in names)
                        for names in by_path.values())
    backward_cost = (BACKWARD_FIXED_FACTOR + 1.0) * total_wgrad \
        + sum(cc.coll_price(a) * _ring(s) * unique_pbytes
              for a, s in mesh_data_axes(ms))

    groups: list[GroupPlan] = []
    for path, names in sorted(by_path.items()):
        if len(names) == 1:
            mode = "single"
            sum_method = "stash" if layers[names[0]].stash else "contrib"
        else:
            ks = sorted((metas[n].kind, metas[n].w_transposed) for n in names)
            mode = ("tied" if ks == [("dense", True), ("embed", False)]
                    and len(names) == 2 else "group_pe")
            if mode == "tied":
                n_e = next(n for n in names if metas[n].kind == "embed")
                if layers[n_e].norm_method == "pe":
                    # Small tied table: materializing the summed grad once
                    # beats segsum + Gram + the cross term, and stashes.
                    mode = "group_pe"
            # group_pe stashes the summed per-example grad during the norm
            # phase (bk contracts it per member); tied contracts per member.
            sum_method = ("stash" if mode == "group_pe" and strategy == "auto"
                          else "contrib")
        if strategy == "ghost":
            sum_method = "backward"
        groups.append(GroupPlan(path, tuple(names), mode, sum_method))

    # All stashes live together from the norm phase to the sum phase, so
    # the budget is charged cumulatively; groups past it fall back to a
    # transient norm + phase-2 contraction (one layer's scratch at a time).
    running = 0.0
    for i, g in enumerate(groups):
        if g.sum_method != "stash":
            continue
        # members of a group share one parameter, so a group stashes one
        # (B, *param) tree: the largest member estimate, not the sum.
        gb = max(layers[n].stash_bytes for n in g.members)
        if running + gb > mem_budget:
            groups[i] = dataclasses.replace(g, sum_method="contrib")
            for n in g.members:
                lp = layers[n]
                # Re-decide the norm under no-stash economics: without the
                # free sum, the stash-optimal method may no longer win.
                fb = lp.fallback_norm or lp.norm_method
                layers[n] = dataclasses.replace(lp, stash=False,
                                                norm_method=fb)
        else:
            running += gb

    # Greedy backward set: groups whose contraction is dearer than their
    # wgrad share, kept only if the replaced contractions pay for the
    # whole extra backward.  Never under a non-flat clipping mode: one
    # weighted backward cannot realize per-layer coefficients, and stale
    # coefficients make every contraction direct (no phase barrier to
    # amortize a backward against).
    candidates: list[tuple[float, int]] = []
    if clip_mode == "flat" and strategy == "auto":
        for i, g in enumerate(groups):
            if g.sum_method != "contrib":
                continue
            cost_c = sum(layers[n].contrib_flops for n in g.members)
            cost_b = sum(layers[n].wgrad_flops for n in g.members)
            if cost_c > cost_b:
                candidates.append((cost_c, i))

    saving = sum(s for s, _ in candidates)
    needs_backward = strategy == "ghost" or saving > backward_cost
    if needs_backward:
        for _, gi in candidates:
            groups[gi] = dataclasses.replace(groups[gi],
                                             sum_method="backward")

    # Stale coefficients are step-invariant inside the pass: mark the
    # Gram-realized dense/conv layers for the fused single-pass
    # norm+contrib (the execution routes them through gram_norm_fused).
    # Only single-tap groups fuse — tied/shared-path groups keep their
    # cross-term norm algebra — and only unscanned convs (the fused conv
    # path has no stacked-axis handling).
    if clip_mode == "stale" and clip_fused:
        single = {g.members[0] for g in groups if len(g.members) == 1}
        for name, lp in layers.items():
            if name not in single or lp.stash:
                continue
            fusable = (
                (lp.kind == "dense"
                 and lp.norm_method in ("gram", "pallas"))
                or (lp.kind == "conv" and metas[name].scanned == 0
                    and lp.norm_method in ("ghost", "pallas")))
            if fusable:
                layers[name] = dataclasses.replace(lp, fused=True)

    # Final per-layer collective prediction for the *chosen* realization,
    # broken out per mesh axis.  Data axes carry the norm phase (stash
    # movement vs the scalar all-reduce of the *global* (B,) norms, the
    # same term _scal_cost charged during selection) plus this layer's
    # share of its group's grad-sync psum — one sync per parameter, split
    # across the taps that share it, doubled for weighted-backward
    # groups.  Model axes carry the partial-norm psum of tensor-sharded
    # layers: their (B,) per-example norms are assembled from partial
    # Grams living on every model shard.
    if ms:
        for g in groups:
            group_pb = max(layers[n].param_bytes for n in g.members)
            sync_each = group_pb \
                * (2.0 if g.sum_method == "backward" else 1.0) \
                / len(g.members)
            for name in g.members:
                lp = layers[name]
                norm_bytes = (lp.stash_bytes if lp.stash
                              else lp.ex_per_dev * d * BYTES)
                by_axis = []
                for a, s in ms:
                    r = _ring(s)
                    if a in DATA_AXIS_NAMES:
                        b = (norm_bytes + sync_each) * r
                    else:
                        b = (lp.ex_per_dev * d * BYTES * r
                             if lp.model_shards > 1 else 0.0)
                    if b > 0.0:
                        by_axis.append((a, b))
                layers[name] = dataclasses.replace(
                    lp, coll_bytes=sum(b for _, b in by_axis),
                    coll_bytes_by_axis=tuple(by_axis))

    capture_bytes = 0.0
    for name in metas:
        capture_bytes += sum(_nbytes(leaf)
                             for leaf in jax.tree.leaves(cap_shapes[name]))
        ts = tap_shapes.get(name)
        if ts is not None:
            capture_bytes += 2.0 * _nbytes(ts)   # tap zeros + cotangent
    capture_bytes /= d   # captures are batch-sharded: per-device share

    axis_totals: dict[str, float] = {}
    for lp in layers.values():
        for a, b in lp.coll_bytes_by_axis:
            axis_totals[a] = axis_totals.get(a, 0.0) + b

    return ExecPlan(
        groups=tuple(groups), layers=layers, metas=metas,
        make_taps=make_taps, needs_backward=needs_backward,
        total_norm_flops=sum(lp.norm_flops for lp in layers.values()),
        total_contrib_flops=sum(lp.contrib_flops for lp in layers.values()),
        tap_shapes=dict(tap_shapes), capture_bytes=capture_bytes,
        mesh=ms, clip_mode=clip_mode, calibration=cc.calibration,
        total_coll_bytes=sum(lp.coll_bytes for lp in layers.values()),
        total_coll_bytes_by_axis=tuple(
            (a, axis_totals[a]) for a, _ in ms if a in axis_totals))


# ---------------------------------------------------------------------------
# Plan cache: (model identity, batch/param shapes, knobs) -> ExecPlan
#
# probe() re-traces the whole model; caching the probe + plan makes the
# steady-state auto path exactly one forward + one backward per step.


_PLAN_CACHE: "OrderedDict[tuple, ExecPlan]" = OrderedDict()


def _fn_ident(apply_fn) -> tuple:
    self = getattr(apply_fn, "__self__", None)
    if self is not None:
        return (id(self), getattr(apply_fn, "__name__", ""))
    return (id(apply_fn), "")


def _shape_sig(tree) -> tuple:
    return tuple(
        (jax.tree_util.keystr(kp), tuple(leaf.shape), str(leaf.dtype))
        for kp, leaf in jax.tree_util.tree_leaves_with_path(tree))


def plan_cache_key(apply_fn, params, batch, opts: tuple) -> tuple:
    return (_fn_ident(apply_fn), _shape_sig(batch), _shape_sig(params), opts)


@functools.lru_cache(maxsize=1)
def code_fingerprint() -> str:
    """Hash of the model/pipeline *sources* (``repro.models`` and
    ``repro.core`` package files).  Folded into every plan fingerprint so
    a plan-store entry produced by different code — a realization whose
    cost or semantics changed since the plan was serialized — fails the
    fingerprint check instead of silently executing under a stale plan."""
    import repro.core
    import repro.models
    h = hashlib.sha1()
    for pkg in (repro.core, repro.models):
        # __path__ (not __file__) also covers namespace packages.
        root = pathlib.Path(next(iter(pkg.__path__)))
        for path in sorted(root.rglob("*.py")):
            h.update(str(path.relative_to(root.parent)).encode())
            h.update(path.read_bytes())
    return h.hexdigest()[:12]


def model_fingerprint(apply_fn, params, batch, opts: tuple = ()) -> str:
    """Cross-process-stable plan identity: model qualname + batch/param
    shape signature + planner knobs + the model-code hash.  Unlike the
    in-process cache key this never uses ``id()``, so a plan exported
    from one process keys the same model in another — but only while the
    sources match (see :func:`code_fingerprint`)."""
    owner = getattr(apply_fn, "__self__", None)
    if owner is not None:
        ident = type(owner).__module__ + "." + type(owner).__qualname__
    else:
        ident = (getattr(apply_fn, "__module__", "") + "."
                 + getattr(apply_fn, "__qualname__", "<fn>"))
    payload = repr((ident, _shape_sig(batch), _shape_sig(params), opts,
                    code_fingerprint()))
    return hashlib.sha1(payload.encode()).hexdigest()[:16]


def clear_plan_cache():
    _PLAN_CACHE.clear()


def plan_cache_info() -> dict:
    return {"size": len(_PLAN_CACHE), "store": len(_PLAN_STORE)}


# Cross-process plan store: fingerprint -> deserialized ExecPlan.  Filled by
# load_plan_store(); consulted by get_plan() before any probe, so a process
# that pre-loads its plans (serving, dry-run verification) never re-traces
# the model for planning.

_PLAN_STORE: dict[str, ExecPlan] = {}


def register_plan(plan: ExecPlan):
    if not plan.fingerprint:
        raise ValueError("plan has no fingerprint; build it via get_plan()")
    _PLAN_STORE[plan.fingerprint] = plan


def clear_plan_store():
    _PLAN_STORE.clear()


def save_plan_store(path: str, plans, extra: dict | None = None,
                    calibrations=None):
    """Write plans (+ optional extra metadata) as one JSON document.

    ``calibrations`` (iterable of ``repro.calibrate.Calibration``)
    persists measured constants alongside the plans; ``None``
    auto-collects every registered calibration whose digest some plan
    was priced under, so a store written after calibrated planning
    round-trips the constants it depends on."""
    plans = list(plans)
    if calibrations is None:
        try:
            from repro.calibrate import table as _ct
        except ImportError:       # pragma: no cover - calibrate ships
            calibrations = ()
        else:
            used = {p.calibration for p in plans if p.calibration}
            calibrations = [c for c in _ct.registered()
                            if c.digest() in used]
    doc = {"format": PLAN_FORMAT_VERSION,
           "plans": [p.to_payload() for p in plans]}
    calibrations = list(calibrations)
    if calibrations:
        doc["calibrations"] = [c.to_payload() for c in calibrations]
    if extra:
        doc.update(extra)
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def load_plan_store(path: str) -> int:
    """Load a plan JSON document into the store; returns the plan count.
    Calibrations persisted with the store are validated (named
    ``CalibrationError`` subclasses on tampered blobs — wrong rates are
    rejected here; hardware/mesh validation happens at use) and
    registered before the plans, so calibrated fingerprints resolve."""
    with open(path) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and doc.get("calibrations"):
        from repro.calibrate import table as _ct
        for cp in doc["calibrations"]:
            _ct.register(_ct.Calibration.from_payload(cp))
    plans = doc["plans"] if isinstance(doc, dict) else doc
    for p in plans:
        register_plan(ExecPlan.from_payload(p))
    return len(plans)


def _sig_summary(sig) -> str:
    return ", ".join(f"{k}{tuple(s)}:{dt}" for k, s, dt in sig) or "(empty)"


def check_plan_matches(plan: ExecPlan, *, fingerprint: str | None = None,
                       mesh=None, batch_sig=None, clip_mode: str | None = None,
                       calibration=None):
    """Validate a deserialized/injected plan against the live context,
    naming the offending field — mesh shape, batch shape, clipping mode,
    calibration, or fingerprint — so a stale plan fails loudly instead
    of executing a stale layout.  ``calibration`` may be a Calibration,
    its digest string, or ``""`` to assert analytic constants."""
    if calibration is not None:
        want = (calibration if isinstance(calibration, str)
                else calibration.digest())
        if plan.calibration != want:
            def _label(d):
                return f"measured constants {d}" if d else "analytic constants"
            raise ValueError(
                f"stale ExecPlan: calibration mismatch — plan "
                f"{plan.fingerprint or '<unfingerprinted>'} was priced "
                f"under {_label(plan.calibration)}, this process plans "
                f"under {_label(want)}; re-calibrate or re-plan")
    if clip_mode is not None and plan.clip_mode != clip_mode:
        raise ValueError(
            f"stale ExecPlan: clipping mode mismatch — plan "
            f"{plan.fingerprint or '<unfingerprinted>'} was built for "
            f"clipping mode {plan.clip_mode!r}, this process clips "
            f"{clip_mode!r}; re-plan for this policy")
    if mesh is not None:
        ms = mesh_axes(mesh)
        if tuple(plan.mesh) != ms:
            raise ValueError(
                f"stale ExecPlan: mesh shape mismatch — plan "
                f"{plan.fingerprint or '<unfingerprinted>'} was built for "
                f"mesh {format_mesh(tuple(plan.mesh))}, this process runs "
                f"{format_mesh(ms)}; re-plan for this topology")
    if batch_sig is not None and plan.batch_sig \
            and tuple(plan.batch_sig) != tuple(batch_sig):
        raise ValueError(
            f"stale ExecPlan: batch shape mismatch — plan "
            f"{plan.fingerprint or '<unfingerprinted>'} was built for "
            f"[{_sig_summary(plan.batch_sig)}], this step feeds "
            f"[{_sig_summary(batch_sig)}]")
    if fingerprint and plan.fingerprint and plan.fingerprint != fingerprint:
        raise ValueError(
            f"stale ExecPlan: fingerprint mismatch — plan "
            f"{plan.fingerprint} != expected {fingerprint} (model code, "
            f"param shapes, or planner knobs changed)")


def _opts_tuple(norm_method, embed_method, conv_norm, mem_budget,
                overrides, mesh, clip_mode="flat", clip_fused=True,
                calibration=None, conv_impl="auto", strategy="auto") -> tuple:
    ms = mesh_axes(mesh)
    calib = _resolve_calibration(calibration, ms)
    return (norm_method, embed_method, conv_norm, mem_budget,
            normalize_overrides(overrides), ms,
            (str(clip_mode), bool(clip_fused)),
            "" if calib is None else calib.digest(), conv_impl, strategy)


def plan_fingerprint(apply_fn, params, batch, *, norm_method: str = "auto",
                     embed_method: str = "auto", conv_norm: str = "auto",
                     mem_budget: int = STREAM_MEM_BUDGET,
                     overrides=None, mesh=None, clip_mode: str = "flat",
                     clip_fused: bool = True, calibration=None,
                     conv_impl: str = "auto", strategy: str = "auto") -> str:
    """The fingerprint :func:`get_plan` would key this request on — same
    knob normalization, no probe."""
    return model_fingerprint(
        apply_fn, params, batch,
        _opts_tuple(norm_method, embed_method, conv_norm, mem_budget,
                    overrides, mesh, clip_mode, clip_fused, calibration,
                    conv_impl, strategy))


def get_plan(apply_fn, params, batch, *, norm_method: str = "auto",
             embed_method: str = "auto", conv_norm: str = "auto",
             mem_budget: int = STREAM_MEM_BUDGET,
             overrides=None, mesh=None, clip_mode: str = "flat",
             clip_fused: bool = True, calibration=None,
             conv_impl: str = "auto", strategy: str = "auto") -> ExecPlan:
    """Cached planner entry point.  The anchor reference pinned in the
    cached plan keeps ``id(apply_fn.__self__)`` stable for the entry's
    lifetime, so a recycled id can never alias a different model.  A
    fingerprint hit in the cross-process plan store short-circuits the
    probe entirely.  ``mesh`` participates in both the cache key and the
    fingerprint, so plans are topology-keyed; a store that holds this
    batch's plan for a *different* topology raises instead of silently
    re-planning over a stale layout.  ``calibration`` (explicit or the
    registered one for this mesh) participates the same way: its digest
    keys the cache and the fingerprint, so a plan priced under stale
    measured constants fails safe exactly like one built from stale
    code."""
    opts = _opts_tuple(norm_method, embed_method, conv_norm, mem_budget,
                       overrides, mesh, clip_mode, clip_fused, calibration,
                       conv_impl, strategy)
    ov, ms = opts[4], opts[5]
    key = plan_cache_key(apply_fn, params, batch, opts)
    plan = _PLAN_CACHE.get(key)
    if plan is not None:
        _PLAN_CACHE.move_to_end(key)
        return plan
    fp = model_fingerprint(apply_fn, params, batch, opts)
    plan = _PLAN_STORE.get(fp)
    if plan is None:
        sig = _shape_sig(batch)
        for cand in _PLAN_STORE.values():
            if tuple(cand.batch_sig) != sig or tuple(cand.mesh) == ms:
                continue
            # Only a store entry that is *this* request's plan on another
            # topology blocks planning: re-key the request under the
            # candidate's mesh and compare fingerprints, so an unrelated
            # model that merely shares the batch shape never trips this.
            cand_opts = _opts_tuple(
                norm_method, embed_method, conv_norm, mem_budget,
                overrides, tuple(cand.mesh), clip_mode, clip_fused,
                calibration, conv_impl, strategy)
            if cand.fingerprint == model_fingerprint(apply_fn, params,
                                                     batch, cand_opts):
                check_plan_matches(cand, mesh=ms)
        make_taps, metas, tap_shapes, cap_shapes = probe(
            apply_fn, params, batch, return_captures=True)
        plan = plan_execution(
            metas, cap_shapes, tap_shapes, make_taps, params,
            norm_method=norm_method, embed_method=embed_method,
            conv_norm=conv_norm, mem_budget=mem_budget, overrides=ov,
            mesh=ms, clip_mode=clip_mode, clip_fused=clip_fused,
            calibration=calibration, conv_impl=conv_impl, strategy=strategy)
        plan = dataclasses.replace(plan, fingerprint=fp, batch_sig=sig)
    object.__setattr__(plan, "_anchor", getattr(apply_fn, "__self__",
                                                apply_fn))
    _PLAN_CACHE[key] = plan
    while len(_PLAN_CACHE) > PLAN_CACHE_SIZE:
        _PLAN_CACHE.popitem(last=False)
    return plan


# ---------------------------------------------------------------------------
# Plan-driven microbatch scheduling


MICROBATCH_MEM_BUDGET = STREAM_MEM_BUDGET


def auto_microbatches(plan: ExecPlan, batch_size: int,
                      mem_budget: int | None = None) -> int:
    """Microbatch count from the plan's peak-memory estimates: the smallest
    divisor of ``batch_size`` whose per-microbatch peak (captures + tap
    cotangents + coexisting stashes — all linear in the leading batch axis)
    fits the budget.  Falls back to fully-sequential (``batch_size``) when
    even single-example microbatches estimate over budget."""
    budget = float(mem_budget or MICROBATCH_MEM_BUDGET)
    need = plan.capture_bytes + plan.peak_stash_bytes()
    B = max(int(batch_size), 1)
    m = 1
    while m < B and need / m > budget:
        m += 1
        while B % m and m < B:
            m += 1
    return m


# ---------------------------------------------------------------------------
# Predicted step cost: what the mispredict loop compares measurements
# against.  Priced in the same FLOP-equivalents the planner selects by,
# then converted to seconds through the calibrated (or analytic) rate.


def predicted_step_flops(plan: ExecPlan, cc: CostConstants | None = None
                         ) -> float:
    """Per-device FLOP-equivalents of one private step under this plan:
    forward + backward (≈ 2 wgrad shares) + wgrad + the plan's norm and
    contraction phases + the weighted second backward when taken + the
    wire price of the predicted collective bytes."""
    cc = cc or ANALYTIC_CONSTANTS
    total_wgrad = sum(lp.wgrad_flops for lp in plan.layers.values())
    flops = 3.0 * total_wgrad \
        + plan.total_norm_flops + plan.total_contrib_flops
    if plan.needs_backward:
        flops += (BACKWARD_FIXED_FACTOR + 1.0) * total_wgrad
    if plan.total_coll_bytes_by_axis:
        flops += sum(cc.coll_price(a) * b
                     for a, b in plan.total_coll_bytes_by_axis)
    else:
        flops += cc.collective_flops_per_byte * plan.total_coll_bytes
    return flops


def predicted_step_seconds(plan: ExecPlan, calibration=None) -> float:
    """Predicted wall-clock of one step: :func:`predicted_step_flops`
    under the plan's cost constants, over the (calibrated or analytic)
    FLOP rate."""
    cc = resolve_cost_constants(calibration, plan.mesh)
    return predicted_step_flops(plan, cc) / cc.flops_per_second


def planner_verdict(mesh_plan: ExecPlan, base_plan: ExecPlan,
                    calibration=None) -> str:
    """Judge a sharded plan against its unsharded counterpart with
    calibrated eyes: ``"sharded"`` when the mesh plan's predicted
    per-device step time beats the single-device plan's, else
    ``"unsharded"`` — the planner either fixes the plan or proves
    unsharded is right."""
    mesh_s = predicted_step_seconds(mesh_plan, calibration)
    base_s = predicted_step_seconds(base_plan, calibration)
    return "sharded" if mesh_s < base_s else "unsharded"
