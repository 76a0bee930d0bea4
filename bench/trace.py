"""Reduction of a JAX profiler trace to the per-layer metrics.

A traced run records, on one clock, the device operations of every chip
(the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane) and the
benchmark's own host spans (``bench.window``, ``bench.feed``,
``bench.dispatch``, ``bench.wait``, ``bench.nonprivate``), written with
``jax.profiler.TraceAnnotation`` around its calls into the program.

On a TPU the name of a device operation is its HLO instruction, ``%name =
result-shape opcode(operands), attributes``, and each operation is tagged
from it: ``collective`` for all-reduce, all-gather, reduce-scatter,
collective-permute and all-to-all, and ``pe_conv`` for an operation whose
result holds one convolution layer's per-example weight gradients.  The
HLO attribute that marks the grouped convolution before compilation
(``feature_group_count`` > 1) is gone after it: the TPU compiler rewrites
that convolution into a batched one fused with the squared-norm reduction,
so the result's size is what identifies it.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                  "collective-permute", "all-to-all")
BENCH_SPANS = ("bench.feed", "bench.dispatch", "bench.wait",
               "bench.nonprivate", "bench.window")


@dataclasses.dataclass(frozen=True)
class Op:
    device: int
    name: str
    start: float          # seconds on the trace's clock
    end: float
    tags: frozenset = frozenset()


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


def union(intervals) -> list:
    """Sorted, disjoint union of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def subtract(a, b) -> list:
    """Parts of the disjoint sorted intervals ``a`` not covered by ``b``."""
    out = []
    b = list(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


_LAYOUT = re.compile(r"\{[^{}]*\}")
_HEAD = re.compile(r"^%?([\w.\-]+) = (\([^()]*\)|\w+\[[\d,]*\])\s+"
                   r"([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"\w+\[([\d,]*)\]")


def parse_op(text: str):
    """(name, [element count of each result], opcode) of a trace's device
    operation, whose name is its HLO instruction: ``%name = shape
    opcode(operands), attributes``; ``(text, [], None)`` for another
    name."""
    flat = text
    while True:
        stripped = _LAYOUT.sub("", flat)
        if stripped == flat:
            break
        flat = stripped
    m = _HEAD.match(flat)
    if not m:
        return text, [], None
    sizes = []
    for dims in _SHAPE.findall(m.group(2)):
        n = 1
        for d in filter(None, dims.split(",")):
            n *= int(d)
        sizes.append(n)
    return m.group(1), sizes, m.group(3)


def short_name(text: str) -> str:
    """``name = result shape`` of an instruction, layouts left out."""
    name, _, opcode = parse_op(text)
    if opcode is None:
        return text[:120]
    flat = text.split(" = ", 1)[1]
    while _LAYOUT.search(flat):
        flat = _LAYOUT.sub("", flat)
    return f"%{name} = {flat.split(' ' + opcode + '(')[0]} {opcode}"[:120]


def tag_op(text: str, pe_sizes: frozenset) -> frozenset:
    """Tags of one device operation.  ``collective``: its opcode, or the
    name of a fusion, is a collective's (async halves included).
    ``pe_conv``: one of its results holds as many elements as one
    convolution layer's per-example weight gradients on this device
    (``pe_sizes``), whatever the compiler made of the grouped
    convolution that computes them."""
    name, sizes, op = parse_op(text)
    tags = set()
    if op is not None:
        base = re.sub(r"-(start|done)$", "", op)
        if base in COLLECTIVE_OPS or any(
                re.match(rf"^{c}(\b|[.\-_])", name) for c in COLLECTIVE_OPS):
            tags.add("collective")
        if pe_sizes.intersection(sizes):
            tags.add("pe_conv")
    return frozenset(tags)


@dataclasses.dataclass
class Trace:
    """What the per-layer readers read of one traced run."""

    ops: list
    spans: list
    n_devices: int
    nonprivate_steps: int

    def __post_init__(self):
        self._by_device = {d: sorted((o for o in self.ops if o.device == d),
                                     key=lambda o: o.start)
                           for d in range(self.n_devices)}

    def span(self, name: str):
        found = [s for s in self.spans if s.name == name]
        return found[0] if found else None

    @property
    def window(self):
        return self.span("bench.window")

    @property
    def window_s(self) -> float:
        w = self.window
        return w.end - w.start if w else 0.0

    def _busy(self, device: int, lo: float, hi: float, tag=None,
              without=None) -> list:
        return clip(union((o.start, o.end) for o in self._by_device[device]
                          if (tag is None or tag in o.tags)
                          and (without is None or without not in o.tags)),
                    lo, hi)

    def _span_of(self, which: str):
        return self.window if which == "private" else \
            self.span("bench.nonprivate")

    @property
    def busy_s(self) -> float:
        """Busy seconds in the window, averaged over the devices."""
        w = self.window
        if not w:
            return 0.0
        return sum(length(self._busy(d, w.start, w.end))
                   for d in range(self.n_devices)) / self.n_devices

    def busy_per_run(self, which: str, runs: int) -> float | None:
        s = self._span_of(which)
        if not s or runs <= 0:
            return None
        busy = sum(length(self._busy(d, s.start, s.end))
                   for d in range(self.n_devices)) / self.n_devices
        return busy / runs if busy > 0 else None

    def share_of(self, which: str, tag: str) -> float | None:
        """Share of busy time in ``which``'s span in ops tagged ``tag``;
        ``None`` where no op has the tag."""
        s = self._span_of(which)
        if not s or not any(tag in o.tags for o in self.ops):
            return None
        busy = tagged = 0.0
        for d in range(self.n_devices):
            busy += length(self._busy(d, s.start, s.end))
            tagged += length(self._busy(d, s.start, s.end, tag))
        return tagged / busy if busy > 0 else None

    def exposed_collective_share(self) -> float | None:
        """Share of the window in which a collective runs on a device and
        nothing else does, averaged over devices; ``None`` without
        collectives."""
        w = self.window
        if not w or not any("collective" in o.tags for o in self.ops):
            return None
        total = 0.0
        for d in range(self.n_devices):
            coll = self._busy(d, w.start, w.end, tag="collective")
            other = self._busy(d, w.start, w.end, without="collective")
            total += length(subtract(coll, other))
        return total / self.n_devices / (w.end - w.start)

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time in the window (seconds
        per device), and the longest idle gaps on device 0, each labelled
        by the innermost host span open at its middle."""
        w = self.window
        if not w:
            return {"device_ops": [], "idle_gaps": []}
        per_name = {}
        for o in self.ops:
            s, e = max(o.start, w.start), min(o.end, w.end)
            if e > s:
                per_name[o.name] = per_name.get(o.name, 0.0) + (e - s)
        ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:top]
        busy = self._busy(0, w.start, w.end)
        gaps = subtract([(w.start, w.end)], busy)
        inner = [s for s in self.spans if s.name != "bench.window"]
        labelled = []
        for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
            mid = (s + e) / 2
            open_ = [sp for sp in inner if sp.start <= mid < sp.end]
            label = min(open_, key=lambda sp: sp.end - sp.start).name \
                if open_ else "host: no bench span"
            labelled.append([label, e - s])
        return {"device_ops": [[short_name(n), t / self.n_devices]
                               for n, t in ops],
                "idle_gaps": labelled}


def from_profile(path: str, n_devices: int, pe_sizes: frozenset,
                 nonprivate_steps: int) -> Trace:
    """Read an ``.xplane.pb`` with ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops, spans = [], []
    tag_cache = {}
    for plane in pd.planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < n_devices:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    if ev.name not in tag_cache:
                        tag_cache[ev.name] = tag_op(ev.name, pe_sizes)
                    ops.append(Op(dev, ev.name, ev.start_ns * 1e-9,
                                  ev.end_ns * 1e-9, tag_cache[ev.name]))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in BENCH_SPANS:
                        spans.append(Span(ev.name, ev.start_ns * 1e-9,
                                          ev.end_ns * 1e-9))
    return Trace(ops=ops, spans=spans, n_devices=n_devices,
                 nonprivate_steps=nonprivate_steps)


def load(trace_dir: str, n_devices: int, pe_sizes: frozenset,
         nonprivate_steps: int) -> Trace:
    """The trace written under ``trace_dir`` by ``jax.profiler``."""
    return from_profile(find_profile(trace_dir), n_devices, pe_sizes,
                        nonprivate_steps)


def find_profile(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, "
                           f"found {files}")
    return files[0]


@dataclasses.dataclass
class Context:
    """What a per-layer reader gets: the cell, the window's host-clock
    numbers and the reduced trace."""

    cell: object
    devices: int
    batch: int
    steps: int
    window_s: float
    dispatch_s: list
    peaks: dict
    trace: Trace
