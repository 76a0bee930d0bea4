"""Logical-axis sharding rules (t5x-style) + activation constraints.

Models annotate activations with *logical* axes ("batch", "seq", "embed",
"heads", "mlp", "vocab", "expert", "kv"); parameters carry logical axis
tuples built at init time.  A rules table maps logical axes to mesh axes.
Outside a mesh context every annotation is a no-op, so models stay
mesh-agnostic.

``PrivacyEngine(param_axes=...)`` routes its params — and the adamw/sgdm
optimizer moments, which inherit the param layout — through
:func:`param_sharding` whenever the mesh has a ``model`` axis, so the 2D
(data × model) private step executes tensor-sharded end to end; the
``shapes_tree`` divisibility fallback is what lets odd-width heads stay
replicated next to a sharded trunk (see ``core.engine._step_shardings``).
"""
from __future__ import annotations

import contextlib
import threading

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_state = threading.local()

# Default production rules.  "batch" maps to all pure-data axes; FSDP
# additionally shards the "embed"/"ff_in" param axes over the data axes.
ACT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",
    "kv": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "state": None,
    "frames": None,
}

PARAM_RULES = {
    "embed": None,
    "heads": "model",
    "kv": None,
    "mlp": "model",
    "vocab": "model",
    "expert": "model",
    "layer": None,
    "conv_k": None,
    "state": None,
    "qrank": None,
    "kvrank": None,
}

FSDP_PARAM_RULES = dict(PARAM_RULES, embed=("pod", "data"))


def _axes_to_spec(axes: tuple, rules: dict, mesh: Mesh,
                  shape: tuple | None = None) -> P:
    names = []
    used = set()
    for i, ax in enumerate(axes):
        m = rules.get(ax) if ax is not None else None
        # Drop mesh axes not present in this mesh, already used, or not
        # dividing the dimension.
        if m is None:
            names.append(None)
            continue
        ms = (m,) if isinstance(m, str) else tuple(m)
        ms = tuple(x for x in ms if x in mesh.axis_names and x not in used)
        if shape is not None and ms:
            total = 1
            for x in ms:
                total *= mesh.shape[x]
            if shape[i] % total != 0:
                # try the single largest dividing prefix
                ms = tuple(x for x in ms
                           if shape[i] % mesh.shape[x] == 0)[:1]
                if ms and shape[i] % mesh.shape[ms[0]] != 0:
                    ms = ()
        used.update(ms)
        if not ms:
            names.append(None)
        elif len(ms) == 1:
            names.append(ms[0])
        else:
            names.append(ms)
    while names and names[-1] is None:
        names.pop()
    return P(*names)


@contextlib.contextmanager
def mesh_rules(mesh: Mesh, act_rules: dict | None = None):
    prev = getattr(_state, "ctx", None)
    _state.ctx = (mesh, act_rules or ACT_RULES)
    try:
        yield
    finally:
        _state.ctx = prev


def shard_act(x, *axes):
    """Constrain an activation's sharding if inside a mesh_rules context."""
    ctx = getattr(_state, "ctx", None)
    if ctx is None:
        return x
    mesh, rules = ctx
    spec = _axes_to_spec(axes, rules, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_sharding(axes_tree, mesh: Mesh, *, fsdp: bool = False,
                   shapes_tree=None):
    """Map a logical-axes pytree to NamedShardings.  With ``shapes_tree``
    (parallel pytree of array/SDS leaves) mesh axes that do not divide the
    dimension are dropped instead of erroring (e.g. 4 heads on a 16-way
    model axis stay replicated)."""
    rules = FSDP_PARAM_RULES if fsdp else PARAM_RULES
    is_axes = lambda x: isinstance(x, tuple)
    if shapes_tree is None:
        return jax.tree.map(
            lambda axes: NamedSharding(mesh, _axes_to_spec(axes, rules, mesh)),
            axes_tree, is_leaf=is_axes)
    return jax.tree.map(
        lambda axes, leaf: NamedSharding(
            mesh, _axes_to_spec(axes, rules, mesh, tuple(leaf.shape))),
        axes_tree, shapes_tree, is_leaf=is_axes)


def batch_sharding(batch_abstract, mesh: Mesh):
    """Shard every batch leaf's leading axis over the data axes (the same
    axis-name vocabulary the planner's cost model uses)."""
    from repro.launch.mesh import DATA_AXIS_NAMES

    data_axes = tuple(a for a in DATA_AXIS_NAMES if a in mesh.axis_names)
    if not data_axes:
        raise ValueError(
            f"mesh axes {mesh.axis_names} contain no data-parallel axis "
            f"(one of {DATA_AXIS_NAMES}) to shard the batch over")
    spec = P(data_axes if len(data_axes) > 1 else data_axes[0])

    def mk(leaf):
        return NamedSharding(mesh, spec)

    return jax.tree.map(mk, batch_abstract)
