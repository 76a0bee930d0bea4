"""Sharding-safety pass for the mesh-jitted private step.

The pipeline is written in the global view, so the traced jaxpr shows
no collectives — XLA inserts the psums when partitioning.  What *is*
statically checkable is the combination that forces SPMD to insert
them correctly:

  * the declared in/out shardings: batch split over the data axes on
    the leading (example) dim; the PRNG key strictly replicated; and
    params, optimizer state, clip state, and **every output**
    replicated *across the data axes* — partitioning over model axes
    is the tensor-parallel layout and is allowed, but any data-axis
    name in a param/opt/output spec is an error.  Data-replicated
    outputs are the load-bearing half: each shard of the clipped sum
    and the noised update must be bitwise-identical on every data
    replica, which XLA can only realize by all-reducing the per-shard
    partial sums (per-example Gram/norm contributions psum over
    ``model``, scalar norms over the data axes);
  * taint facts from the global graph: the clip decision (the
    ``clip_coef`` marker) is computed from all ``B`` global examples'
    norms — under a sharded batch that norm vector only exists after a
    psum, so "clip sees the global norm" is structural; and the noise
    markers carry **no** example axis — noise attaches to the
    aggregate, which the replicated-output constraint pins to one
    logical draw from the one replicated key, never independent
    per-shard draws (those would inflate the variance by the shard
    count and desynchronize the replicas).
"""
from __future__ import annotations

from typing import List, Optional

from repro.analysis.graph import FlatGraph
from repro.analysis.report import Finding

DATA_AXIS_NAMES = ("data", "pod", "batch", "dp", "fsdp")


def _is_replicated(sh) -> bool:
    spec = getattr(sh, "spec", sh)
    if spec is None:
        return True
    return all(p is None for p in tuple(spec))


def _data_replicated(sh) -> bool:
    """True iff no data axis appears in the spec — replicated across the
    data axes; model-axis partitioning (tensor parallelism) is fine."""
    spec = getattr(sh, "spec", sh)
    if spec is None:
        return True
    for p in tuple(spec):
        if p is None:
            continue
        axes = p if isinstance(p, (tuple, list)) else (p,)
        if any(ax in DATA_AXIS_NAMES for ax in axes):
            return False
    return True


def _leading_data_sharded(sh) -> bool:
    spec = tuple(getattr(sh, "spec", sh) or ())
    if not spec or spec[0] is None:
        return False
    first = spec[0] if isinstance(spec[0], (tuple, list)) else (spec[0],)
    return all(ax in DATA_AXIS_NAMES for ax in first) \
        and all(p is None for p in spec[1:])


def check_sharding(graph: FlatGraph, *, taints, batch_size: int,
                   mesh_axes: tuple, data_size: int,
                   in_shardings=None, out_shardings=None) -> List[Finding]:
    findings: List[Finding] = []
    where = "sharding"
    if not mesh_axes:
        return findings

    if data_size < 1 or batch_size % max(data_size, 1):
        findings.append(Finding(
            "error", "batch_not_divisible",
            f"global batch {batch_size} is not divisible by the mesh's "
            f"data-parallel degree {data_size}", where))

    # -- declared shardings ----------------------------------------------
    if in_shardings is not None:
        import jax
        names = ("params", "opt", "batch", "key", "clip_state")
        for name, tree in zip(names, in_shardings):
            leaves = jax.tree.leaves(tree)
            if name == "batch":
                bad = [s for s in leaves if not _leading_data_sharded(s)]
                if bad:
                    findings.append(Finding(
                        "error", "batch_not_sharded",
                        "a batch leaf is not sharded over the data axes "
                        "on its leading (example) dim — per-example work "
                        "would not be data-parallel", where))
            elif name == "key":
                bad = [s for s in leaves if not _is_replicated(s)]
                if bad:
                    findings.append(Finding(
                        "error", "key_sharded",
                        "key input is not replicated under the mesh — "
                        "per-shard key slices mean per-shard noise draws",
                        where))
            else:
                bad = [s for s in leaves if not _data_replicated(s)]
                if bad:
                    findings.append(Finding(
                        "error", f"{name}_not_replicated",
                        f"a {name} input is sharded over a data axis — "
                        f"params/opt/clip state must be replicated across "
                        f"the data shards (model-axis partitioning is the "
                        f"tensor-parallel layout and is allowed)", where))
    if out_shardings is not None:
        import jax
        bad = [s for s in jax.tree.leaves(out_shardings)
               if not _data_replicated(s)]
        if bad:
            findings.append(Finding(
                "error", "outputs_not_replicated",
                "a step output is sharded over a data axis — every shard "
                "of the clipped+noised update must be identical on every "
                "data replica (the all-reduce XLA inserts to realize that "
                "replication is what sums the per-shard contributions); "
                "model-axis partitioning is allowed", where))

    # -- taint facts on the global graph ----------------------------------
    for node, _ in graph.markers():
        kind = node.params.get("kind")
        if kind == "noise":
            t = taints.get(graph.resolve(node.invars[0])
                           if hasattr(graph, "resolve") else node.invars[0])
            if t is not None and t.batch:
                findings.append(Finding(
                    "error", "noise_per_example",
                    "a noise marker still carries the example axis — "
                    "noise must attach to the aggregate (one draw), not "
                    "to per-example/per-shard values", where))
        elif kind in ("clip_coef", "group_norm"):
            shape = tuple(getattr(node.outvars[0].aval, "shape", ()))
            if shape and batch_size not in shape:
                findings.append(Finding(
                    "error", "clip_not_global",
                    f"{kind} marker has shape {shape} — the clip decision "
                    f"does not cover all {batch_size} global examples "
                    f"(norms must be globally reduced before clipping)",
                    where))

    return findings
