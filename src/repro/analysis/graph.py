"""Jaxpr flattening and slicing for the static DP verifier.

``jax.make_jaxpr`` on the private step yields a ClosedJaxpr whose
interesting structure hides inside nested call equations (``jit``,
``custom_jvp_call``, ``remat``).  :func:`flatten` inlines those into one
topologically ordered node list with variables resolved across call
boundaries, so the analysis passes walk a single graph.  Control-flow
equations that genuinely execute their body differently (``scan``,
``while``, ``cond``, ``pallas_call``) are kept as single nodes but carry
their recursively flattened bodies in ``Node.sub`` — passes that need to
look inside (taint through a scan, marker/noise census) can.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

from jax.extend.core import ClosedJaxpr, Literal, Var

# Call-like primitives whose body is semantically "run once, in place":
# safe to inline into the parent graph.
INLINE_PRIMS = ("jit", "closed_call", "core_call", "call",
                "custom_jvp_call", "custom_vjp_call",
                "custom_jvp_call_jaxpr", "custom_vjp_call_jaxpr",
                "remat", "remat2", "checkpoint")

# Control-flow primitives kept opaque (one node) but with flattened
# bodies attached for recursive passes.
SUBGRAPH_PRIMS = ("scan", "while", "cond", "pallas_call")


@dataclasses.dataclass
class Node:
    """One flattened equation: primitive name, alias-resolved inputs,
    raw outputs, static params, and (for control flow) flattened
    sub-bodies."""

    prim: str
    invars: List[Any]            # Var | Literal, resolved
    outvars: List[Var]
    params: Dict[str, Any]
    sub: Optional[List["FlatGraph"]] = None

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Node({self.prim}, in={len(self.invars)}, "
                f"out={len(self.outvars)})")


def _closed_of(obj) -> Optional[ClosedJaxpr]:
    """Coerce a params entry to a ClosedJaxpr when possible."""
    if obj is None:
        return None
    if isinstance(obj, ClosedJaxpr):
        return obj
    if hasattr(obj, "eqns"):  # an open Jaxpr
        if getattr(obj, "constvars", ()):
            return None
        return ClosedJaxpr(obj, ())
    return None


def _inner_closed(eqn) -> Optional[ClosedJaxpr]:
    p = eqn.params
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        closed = _closed_of(p.get(key))
        if closed is not None:
            return closed
    return None


def _sub_bodies(eqn) -> List["FlatGraph"]:
    p = eqn.params
    bodies = []
    if eqn.primitive.name == "cond":
        for br in p.get("branches", ()):
            c = _closed_of(br)
            if c is not None:
                bodies.append(flatten(c))
        return bodies
    if eqn.primitive.name == "while":
        for key in ("cond_jaxpr", "body_jaxpr"):
            c = _closed_of(p.get(key))
            if c is not None:
                bodies.append(flatten(c))
        return bodies
    c = _inner_closed(eqn)
    if c is not None:
        bodies.append(flatten(c))
    return bodies


class FlatGraph:
    """The flattened view of one ClosedJaxpr."""

    def __init__(self, closed: ClosedJaxpr):
        self.closed = closed
        self.nodes: List[Node] = []
        self.invars: List[Var] = list(closed.jaxpr.invars)
        self.const_vars: Set[Var] = set()
        self._alias: Dict[Var, Any] = {}
        self._flatten_body(closed.jaxpr)
        self.outvars: List[Any] = [self.resolve(v)
                                   for v in closed.jaxpr.outvars]
        self.producer: Dict[Var, Node] = {}
        for node in self.nodes:
            for ov in node.outvars:
                self.producer[ov] = node

    # -- construction ------------------------------------------------------

    def resolve(self, v):
        """Follow cross-call aliases to the canonical producer var."""
        while isinstance(v, Var) and v in self._alias:
            v = self._alias[v]
        return v

    def _flatten_body(self, jaxpr):
        for cv in jaxpr.constvars:
            self.const_vars.add(cv)
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            inner = _inner_closed(eqn) if name in INLINE_PRIMS else None
            if inner is not None:
                n_in = len(inner.jaxpr.invars)
                # Call conventions put any extra (const-like) operands
                # first; bind the *trailing* invars positionally.
                args = list(eqn.invars)[-n_in:] if n_in else []
                for iv, ov in zip(inner.jaxpr.invars, args):
                    self._alias[iv] = self.resolve(ov)
                for cv in inner.jaxpr.constvars:
                    self.const_vars.add(cv)
                self._flatten_body(inner.jaxpr)
                for eo, io in zip(eqn.outvars, inner.jaxpr.outvars):
                    self._alias[eo] = self.resolve(io)
                continue
            sub = _sub_bodies(eqn) if name in SUBGRAPH_PRIMS else None
            self.nodes.append(Node(
                prim=name,
                invars=[self.resolve(v) for v in eqn.invars],
                outvars=list(eqn.outvars),
                params=dict(eqn.params),
                sub=sub or None))

    # -- queries -----------------------------------------------------------

    def iter_nodes(self, recursive: bool = False) -> Iterator[Node]:
        for node in self.nodes:
            yield node
            if recursive and node.sub:
                for g in node.sub:
                    yield from g.iter_nodes(recursive=True)

    def markers(self) -> List[Tuple[Node, "FlatGraph"]]:
        """All ``dp_tag`` nodes, recursively, with their owning graph."""
        out = []
        for node in self.nodes:
            if node.prim == "dp_tag":
                out.append((node, self))
            if node.sub:
                for g in node.sub:
                    out.extend(g.markers())
        return out

    def count_prim(self, name: str) -> int:
        """Occurrences of a primitive, recursively (scan bodies count
        once — the static census, not the dynamic trip count)."""
        return sum(1 for n in self.iter_nodes(recursive=True)
                   if n.prim == name)

    def backward_slice(self, targets) -> Set[Var]:
        """Every var that (transitively) feeds ``targets``.  Control-flow
        nodes are conservative: all inputs feed all outputs."""
        seen: Set[Var] = set()
        stack = [t for t in targets if isinstance(t, Var)]
        while stack:
            v = stack.pop()
            if v in seen:
                continue
            seen.add(v)
            node = self.producer.get(v)
            if node is None:
                continue
            for iv in node.invars:
                if isinstance(iv, Var) and iv not in seen:
                    stack.append(iv)
        return seen


def flatten(closed: ClosedJaxpr) -> FlatGraph:
    return FlatGraph(closed)


def aval_of(v):
    """The abstract value of a Var or Literal."""
    return v.aval
