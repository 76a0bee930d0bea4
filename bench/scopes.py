"""The program's own names in a profiler trace: the ``dp.*`` scope of each
device operation and the ``engine.*`` host spans.

The program names every phase of its private step (``src/repro/core``):
each device operation's metadata carries the ``jax.named_scope`` of the
phase it was compiled from (``dp.capture``, ``dp.norm/<method>/<group>``,
``dp.clip``, ``dp.contrib/<method>/<group>``, ``dp.noise``, ``dp.update``),
and ``PrivacyEngine.private_step`` writes the host spans
``engine.private_step``, ``engine.noise_key``, ``engine.dispatch``,
``engine.absorb_clip_aux`` and, while the step is traced, ``engine.trace``.

``jax.profiler.ProfileData`` gives an event's stats but not its metadata's,
where a TPU trace keeps the op name (stat ``tf_op``, e.g.
``jit(step)/dp.norm/pe/conv8/conv_general_dilated:``).  So the device
planes are read here
from the ``.xplane.pb`` itself, with a small reader of the protobuf wire
format (field numbers of ``tsl/profiler/protobuf/xplane.proto``); host
spans are read with ``ProfileData``, as ``bench/trace.py`` reads them.

``load`` returns a ``ScopedTrace``: a ``bench.trace.Trace`` whose ops carry
their scope, whose spans include the ``engine.*`` spans, and on which every
reader of ``bench/trace.py``'s quantities returns what it returns on the
plain ``Trace`` of the same file.
"""
from __future__ import annotations

import dataclasses
import re
import statistics
import struct

from bench import trace as T

ENGINE_PREFIX = "engine."
PHASES = ("dp.capture", "dp.norm", "dp.clip", "dp.contrib", "dp.noise",
          "dp.update")
_WRAPPER = re.compile(r"[A-Za-z_][\w.]*\(|\)")


# ---------------------------------------------------------------------------
# Protobuf wire format


def _varint(buf: bytes, i: int):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def fields(buf: bytes):
    """(field number, value) of each field of one message: an int for a
    varint or fixed-width field, bytes for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire == 1:
            val, i = struct.unpack_from("<q", buf, i)[0], i + 8
        elif wire == 5:
            val, i = struct.unpack_from("<i", buf, i)[0], i + 4
        else:
            raise ValueError(f"wire type {wire} of field {num} at {i}")
        yield num, val


# XSpace.planes = 1.  XPlane: name 2, lines 3, event_metadata 4 (map entry:
# key 1, value 2), stat_metadata 5 (the same).  XLine: name 2, events 4.
# XEvent: metadata_id 1.  XEventMetadata: id 1, name 2, stats 5.
# XStatMetadata: id 1, name 2.  XStat: metadata_id 1, double 2, uint64 3,
# int64 4, str 5, bytes 6, ref 7 (the id of a stat metadata whose name is
# the value).


def _map(entries, parse):
    out = {}
    for raw in entries:
        key, val = 0, b""
        for num, v in fields(raw):
            if num == 1:
                key = v
            elif num == 2:
                val = v
        out[key] = parse(val)
    return out


def _stat_name(raw: bytes) -> str:
    return next((v.decode() for num, v in fields(raw) if num == 2), "")


def _event_metadata(raw: bytes) -> tuple:
    name, stats = "", []
    for num, v in fields(raw):
        if num == 2:
            name = v.decode()
        elif num == 5:
            stats.append(dict(fields(v)))
    return name, stats


def _str_value(stat: dict, stat_names: dict) -> str:
    """A string stat's value: inline, or the name of the stat metadata it
    refers to."""
    if 5 in stat:
        return stat[5].decode()
    return stat_names.get(stat.get(7), "")


@dataclasses.dataclass
class DeviceOps:
    """One device plane's ``XLA Ops`` events, as the metadata id of each in
    the order of the file, and each metadata id's (name, op name)."""

    events: list
    metadata: dict


def device_planes(data: bytes, n_devices: int) -> dict:
    """``{device: DeviceOps}`` of the ``/device:TPU:<n>`` planes, n below
    ``n_devices``, of a serialized XSpace."""
    out = {}
    for num, raw in fields(data):
        if num != 1:
            continue
        plane = {}
        for pnum, v in fields(raw):
            plane.setdefault(pnum, []).append(v)
        name = plane.get(2, [b""])[0].decode()
        m = re.match(r"^/device:TPU:(\d+)$", name)
        if not m or int(m.group(1)) >= n_devices:
            continue
        stat_names = _map(plane.get(5, []), _stat_name)
        meta = {}
        for mid, (ev_name, stats) in _map(plane.get(4, []),
                                          _event_metadata).items():
            op_name = next((_str_value(s, stat_names) for s in stats
                            if stat_names.get(s.get(1)) == "tf_op"), "")
            meta[mid] = (ev_name, op_name.rsplit(":", 1)[0])
        events = []
        for line_raw in plane.get(3, []):
            line = {}
            for lnum, v in fields(line_raw):
                line.setdefault(lnum, []).append(v)
            if line.get(2, [b""])[0].decode() != "XLA Ops":
                continue
            for ev_raw in line.get(4, []):
                events.append(next((v for num, v in fields(ev_raw)
                                    if num == 1), 0))
        out[int(m.group(1))] = DeviceOps(events=events, metadata=meta)
    return out


# ---------------------------------------------------------------------------
# Scopes


def scope_of(op_name: str) -> str | None:
    """The ``dp.*`` scope path of an op name: ``dp.<phase>``, or
    ``dp.norm/<method>/<group>`` and ``dp.contrib/<method>/<group>`` (but
    ``dp.contrib/backward``), found through JAX's transform wrappers
    (``transpose(jvp(dp.capture))``); ``None`` outside every scope."""
    parts = _WRAPPER.sub("", op_name).split("/")
    for i, part in enumerate(parts):
        if part in PHASES:
            n = 3 if part in ("dp.norm", "dp.contrib") else 1
            if parts[i + 1:i + 2] == ["backward"]:
                n = 2
            return "/".join(parts[i:i + n])
    return None


def under(scope: str | None, prefix: str) -> bool:
    return scope is not None and (scope == prefix
                                  or scope.startswith(prefix + "/"))


@dataclasses.dataclass(frozen=True)
class ScopedOp(T.Op):
    scope: str | None = None


@dataclasses.dataclass
class ScopedTrace(T.Trace):
    """A ``Trace`` whose ops carry their ``dp.*`` scope, and whose spans
    hold the program's ``engine.*`` spans beside the benchmark's."""

    def _scoped(self, device: int, lo: float, hi: float, keep) -> list:
        return T.clip(T.union((o.start, o.end) for o in self._by_device[device]
                              if keep(o.scope)), lo, hi)

    def scope_ms(self, prefixes, runs: int, which: str = "private"):
        """Device ms per run of ``which`` (``private``: the window,
        ``nonprivate``) in operations under any of ``prefixes`` (a scope
        path or a prefix of one): the union of their intervals, clipped to
        the span, averaged over the devices.  ``None`` where no operation
        under them runs in the span."""
        if isinstance(prefixes, str):
            prefixes = (prefixes,)
        s = self._span_of(which)
        if not s or runs <= 0:
            return None

        def keep(scope):
            return any(under(scope, p) for p in prefixes)

        busy = sum(T.length(self._scoped(d, s.start, s.end, keep))
                   for d in range(self.n_devices)) / self.n_devices
        return 1e3 * busy / runs if busy > 0 else None

    def scopes(self, which: str = "private") -> dict:
        """Device seconds per device of each scope path in ``which``'s
        span (operations of one scope do not overlap one another)."""
        s = self._span_of(which)
        out = {}
        if not s:
            return out
        for o in self.ops:
            lo, hi = max(o.start, s.start), min(o.end, s.end)
            if hi > lo and o.scope:
                out[o.scope] = out.get(o.scope, 0.0) + (hi - lo)
        return {k: v / self.n_devices for k, v in out.items()}

    def coverage(self, which: str = "private"):
        """Share of the span's device busy time in operations under some
        ``dp.*`` scope; ``None`` without device operations."""
        s = self._span_of(which)
        if not s:
            return None
        busy = scoped = 0.0
        for d in range(self.n_devices):
            busy += T.length(self._busy(d, s.start, s.end))
            scoped += T.length(self._scoped(d, s.start, s.end,
                                            lambda sc: sc is not None))
        return scoped / busy if busy > 0 else None

    def span_ms(self, name: str) -> list:
        """Durations in ms of the host spans ``name`` inside the window."""
        w = self.window
        if not w:
            return []
        return [1e3 * (s.end - s.start) for s in self.spans
                if s.name == name and w.start <= s.start and s.end <= w.end]

    def breakdown(self, top: int = 10) -> dict:
        """``Trace.breakdown``, with each device operation prefixed by its
        scope path (``dp.norm/pe/conv8 %multiply_reduce_fusion.3 = ...``);
        idle gaps are labelled by the innermost span open, the program's
        ``engine.*`` spans among them."""
        out = super().breakdown(top)
        w = self.window
        if not w:
            return out
        per_op = {}
        for o in self.ops:
            s, e = max(o.start, w.start), min(o.end, w.end)
            if e > s:
                key = (o.scope, o.name)
                per_op[key] = per_op.get(key, 0.0) + (e - s)
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
        out["device_ops"] = [
            [(f"{scope} " if scope else "") + T.short_name(name),
             t / self.n_devices] for (scope, name), t in ops]
        return out


def from_profile(path: str, n_devices: int, pe_sizes: frozenset,
                 nonprivate_steps: int) -> ScopedTrace:
    """Read an ``.xplane.pb`` as ``bench.trace.from_profile`` does, with
    the same times, and each device operation's scope from its
    metadata (the wire-format reader, event by event in the file's
    order); host spans ``bench.*`` and ``engine.*``."""
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        data = f.read()
    planes = device_planes(data, n_devices)
    ops, spans, tags = [], [], {}
    for plane in ProfileData.from_serialized_xspace(data).planes:
        m = re.match(r"^/device:TPU:(\d+)$", plane.name)
        if m and int(m.group(1)) < n_devices:
            dev = int(m.group(1))
            ids = iter(planes[dev].events)
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name, op_name = planes[dev].metadata[next(ids)]
                    if name != ev.name:
                        raise ValueError(f"device {dev}: event {ev.name!r} "
                                         f"read as {name!r}")
                    if name not in tags:
                        tags[name] = T.tag_op(name, pe_sizes)
                    ops.append(ScopedOp(dev, name, ev.start_ns * 1e-9,
                                        ev.end_ns * 1e-9, tags[name],
                                        scope_of(op_name)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in T.BENCH_SPANS or \
                            ev.name.startswith(ENGINE_PREFIX):
                        spans.append(T.Span(ev.name, ev.start_ns * 1e-9,
                                            ev.end_ns * 1e-9))
    return ScopedTrace(ops=ops, spans=spans, n_devices=n_devices,
                       nonprivate_steps=nonprivate_steps)


def load(trace_dir: str, n_devices: int, pe_sizes: frozenset,
         nonprivate_steps: int) -> ScopedTrace:
    """The trace written under ``trace_dir``, as ``bench.trace.load``."""
    return from_profile(T.find_profile(trace_dir), n_devices, pe_sizes,
                        nonprivate_steps)


# ---------------------------------------------------------------------------
# What the scope readers in bench/metrics share


def read_ms(ctx, *prefixes):
    """Device ms per private step under ``prefixes``, or ``None`` where the
    trace carries no scopes or none of them ran in the window."""
    if not isinstance(ctx.trace, ScopedTrace):
        return None
    return ctx.trace.scope_ms(prefixes, ctx.steps)


def median_span_ms(ctx, name: str):
    if not isinstance(ctx.trace, ScopedTrace):
        return None
    found = ctx.trace.span_ms(name)
    return statistics.median(found) if found else None
