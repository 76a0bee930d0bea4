"""The program's scopes and spans read from a profiler trace
(``bench/scopes.py``) and the readers built on them.

``data/toy_step.xplane.pb`` holds three private steps of the toy CNN
planned ``pe`` / ``ghost`` / ``rank1`` and two non-private steps, recorded
on one TPU v5e chip by ``record_toy_trace.py``; ``data/tiny.xplane.pb``
holds a small program without scopes (see ``test_trace.py``)."""
import json
import pathlib
import statistics

import pytest

from bench import profile_cell, scopes as S, spec
from bench import trace as T
from conftest import ROOT

DATA = pathlib.Path(__file__).resolve().parent / "data"
TOY = DATA / "toy_step.xplane.pb"
TOY_STEPS, TOY_NONPRIVATE = 3, 2
EXISTING = ("step.dp_overhead", "realize.grouped_conv_share",
            "device.idle_share", "collective.exposed_share", "loop.host_ms",
            "step.mfu")
NEW = profile_cell.SCOPE_READERS


def _ctx(trace, steps):
    return T.Context(cell=spec.load_cell("vgg16.flat.b32", ROOT), devices=1,
                     batch=8, steps=steps, window_s=trace.window_s,
                     dispatch_s=[0.01, 0.02, 0.03],
                     peaks=spec.peaks("TPU v5 lite", ROOT), trace=trace)


def _read(name, ctx):
    return spec.load_reader(name, ROOT).read(ctx)


def _tiny(cls_from_profile):
    tr = cls_from_profile(str(DATA / "tiny.xplane.pb"), 1,
                          frozenset({2048}), 0)
    dispatches = [s for s in tr.spans if s.name == "bench.dispatch"]
    waits = [s for s in tr.spans if s.name == "bench.wait"]
    tr.spans.append(T.Span("bench.window", dispatches[0].start,
                           waits[-1].end))
    return tr


def test_op_names_parse_to_scope_paths():
    assert S.scope_of("jit(step)/dp.capture/transpose(jvp())/mul") == \
        "dp.capture"
    assert S.scope_of("jit(step)/transpose(jvp(dp.norm/pe/conv8))/mul") == \
        "dp.norm/pe/conv8"
    assert S.scope_of("jit(step)/dp.contrib/backward/jvp()/dot_general") \
        == "dp.contrib/backward"
    assert S.scope_of("jit(step)/dp.contrib/stash/blocks.fc/dot_general") \
        == "dp.contrib/stash/blocks.fc"
    assert S.scope_of("jit(step)/dp.update/mul") == "dp.update"
    assert S.scope_of("jit(step)/dp.nothing/mul") is None
    assert S.scope_of("jit(tiny)/conv_general_dilated") is None
    assert S.under("dp.norm/pe/conv8", "dp.norm/pe")
    assert S.under("dp.norm/pe/conv8", "dp.norm")
    assert not S.under("dp.norm/pe/conv8", "dp.norm/p")
    assert not S.under(None, "dp.norm")


def test_wire_reader_matches_profile_data_on_the_unscoped_trace():
    """On a trace without scopes the scoped reduction is the plain one:
    the same operations at the same times, and every existing reader
    returns what it returned before this reduction existed."""
    plain, scoped = _tiny(T.from_profile), _tiny(S.from_profile)
    assert [(o.device, o.name, o.start, o.end, o.tags) for o in plain.ops] \
        == [(o.device, o.name, o.start, o.end, o.tags) for o in scoped.ops]
    assert {o.scope for o in scoped.ops} == {None}
    pinned = {"device.idle_share": 99.56241697021659,
              "realize.grouped_conv_share": 18.607384193515358,
              "loop.host_ms": 20.0, "step.mfu": 632.7508395027616}
    for name in EXISTING:
        want = _read(name, _ctx(plain, 3))
        assert _read(name, _ctx(scoped, 3)) == want, name
        assert want == pytest.approx(pinned.get(name), rel=1e-12) \
            if name in pinned else want is None, (name, want)
    for name in NEW:
        assert _read(name, _ctx(scoped, 3)) is None, name
        assert _read(name, _ctx(plain, 3)) is None, name


# ---------------------------------------------------------------------------
# A hand-made trace, written in the wire format: one TPU and one host plane.

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _msg(*fields):
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


US = 1_000_000        # picoseconds in a microsecond
# Device ops of one window of two private steps, 0-100 us:
# (start us, end us, op name or None, flops).
HAND_OPS = [(10, 20, "dp.capture/jvp()/mul", 0),
            (20, 40, "transpose(jvp(dp.norm/pe/conv0))/mul", 4_000_000),
            (30, 45, "dp.norm/pe/conv0/reduce_sum", 2_000_000),
            (45, 50, "dp.norm/ghost/conv1/dot_general", 0),
            (50, 52, "dp.norm/rank1/fc0/mul", 0),
            (52, 60, "dp.contrib/stash/conv0/dot_general", 0),
            (60, 62, "dp.clip/div", 0), (62, 66, "dp.noise/add", 0),
            (66, 70, "dp.update/mul", 0), (70, 74, None, 0),
            (110, 120, "dp.capture/mul", 0)]
HAND_SPANS = [("bench.window", 0, 100), ("bench.dispatch", 0, 12),
              ("engine.private_step", 1, 11), ("engine.dispatch", 2, 10),
              ("bench.wait", 72, 100), ("bench.dispatch", 79, 87),
              ("engine.private_step", 80, 86)]


def hand_xspace() -> bytes:
    stat_md = [_msg((1, 1), (2, "tf_op")), _msg((1, 2), (2, "flops")),
               _msg((1, 3), (2, "jit(step)/copy:"))]
    metas, events = [], []
    for i, (start, end, op, flops) in enumerate(HAND_OPS, 1):
        # The unscoped op's name is a reference to a stat's name.
        tf_op = (_msg((1, 1), (7, 3)) if op is None
                 else _msg((1, 1), (5, f"jit(step)/{op}:")))
        meta = _msg((1, i), (2, f"%fusion.{i} = f32[8]{{0}} fusion("
                                f"f32[8]{{0}} %x)"), (5, tf_op),
                    (5, _msg((1, 2), (4, flops))))
        metas.append((4, _msg((1, i), (2, meta))))
        events.append((4, _msg((1, i), (2, start * US),
                               (3, (end - start) * US))))
    line = _msg((1, 1), (2, "XLA Ops"), (3, 1_000_000_000), *events)
    device = _msg((1, 1), (2, "/device:TPU:0"), (3, line), *metas,
                  *[(5, _msg((1, i), (2, m))) for i, m in
                    enumerate(stat_md, 1)])
    names = sorted({n for n, _, _ in HAND_SPANS})
    host_events = [(4, _msg((1, names.index(n) + 1), (2, a * US),
                            (3, (b - a) * US))) for n, a, b in HAND_SPANS]
    host = _msg((1, 2), (2, "/host:CPU"),
                (3, _msg((1, 1), (2, "python"), (3, 1_000_000_000),
                         *host_events)),
                *[(4, _msg((1, i), (2, _msg((1, i), (2, n)))))
                  for i, n in enumerate(names, 1)])
    return _msg((1, device), (1, host))


@pytest.fixture
def hand(tmp_path):
    path = tmp_path / "hand.xplane.pb"
    path.write_bytes(hand_xspace())
    return S.from_profile(str(path), 1, frozenset(), 0)


def test_wire_reader_decodes_scopes_flops_and_spans(hand):
    assert [o.scope for o in hand.ops] == [
        "dp.capture", "dp.norm/pe/conv0", "dp.norm/pe/conv0",
        "dp.norm/ghost/conv1", "dp.norm/rank1/fc0", "dp.contrib/stash/conv0",
        "dp.clip", "dp.noise", "dp.update", None, "dp.capture"]
    assert [(o.start, o.end) for o in hand.ops][1] == \
        pytest.approx((1.000020, 1.000040), abs=1e-9)
    assert sorted({s.name for s in hand.spans}) == sorted(
        {n for n, _, _ in HAND_SPANS})


def test_scope_ms_coverage_and_flops_by_hand(hand):
    ms = 1e-3        # one microsecond in ms
    assert hand.scope_ms("dp.norm/pe", 2) == pytest.approx(25 / 2 * ms)
    assert hand.scope_ms("dp.norm", 2) == pytest.approx(32 / 2 * ms)
    assert hand.scope_ms("dp.capture", 2) == pytest.approx(10 / 2 * ms)
    assert hand.scope_ms(("dp.noise", "dp.update"), 2) == \
        pytest.approx(8 / 2 * ms)
    assert hand.scope_ms("dp.norm/gram", 2) is None
    assert hand.coverage() == pytest.approx(60 / 64)
    assert hand.scopes()["dp.norm/pe/conv0"] == pytest.approx(35e-6)


def test_scope_readers_by_hand(hand):
    got = {name: _read(name, _ctx(hand, 2)) for name in NEW}
    assert got == pytest.approx({
        "step.capture_ms": 5e-3, "realize.pe_ms": 12.5e-3,
        "realize.ghost_ms": 2.5e-3, "realize.rank1_ms": 1e-3,
        "realize.contrib_ms": 4e-3, "noise_opt.ms": 4e-3,
        "engine.host_ms": 8e-3})


def test_breakdown_by_hand(hand):
    out = hand.breakdown()
    assert out["device_ops"][0][0] == \
        "dp.norm/pe/conv0 %fusion.2 = f32[8] fusion"
    assert out["device_ops"][0][1] == pytest.approx(20e-6)
    assert "%fusion.10 = f32[8] fusion" in [n for n, _ in out["device_ops"]]
    # The gap before the first op opens inside the program's dispatch.
    assert out["idle_gaps"] == [["bench.wait", pytest.approx(26e-6)],
                                ["engine.dispatch", pytest.approx(10e-6)]]


@pytest.fixture(scope="module")
def toy():
    if not TOY.is_file():
        pytest.fail(f"{TOY} is missing: record it with "
                    "record_toy_trace.py on a TPU chip")
    return S.from_profile(str(TOY), 1, frozenset(), TOY_NONPRIVATE)


def test_recorded_toy_step_is_small_and_whole(toy):
    assert TOY.stat().st_size < 200_000
    names = {s.name for s in toy.spans}
    assert {"bench.window", "bench.feed", "bench.dispatch", "bench.wait",
            "bench.nonprivate", "engine.private_step", "engine.noise_key",
            "engine.dispatch", "engine.absorb_clip_aux"} <= names
    # Compiled before the trace started: no trace inside the window.
    assert "engine.trace" not in names
    assert len(toy.span_ms("engine.private_step")) == TOY_STEPS


def test_metadata_names_every_phase_of_the_planned_step(toy):
    private = set(toy.scopes("private"))
    planned = {"dp.capture", "dp.norm/pe/conv0", "dp.norm/ghost/conv1",
               "dp.norm/rank1/fc0", "dp.contrib/stash/conv0",
               "dp.contrib/contrib/conv1", "dp.contrib/contrib/fc0",
               "dp.clip", "dp.noise", "dp.update"}
    # The rank-1 norm of fc0, a few products of (8,)-vectors, is fused
    # into operations whose root lies in another phase: no operation
    # carries its scope.
    assert private == planned - {"dp.norm/rank1/fc0"}
    # The non-private step has no private phase.
    assert set(toy.scopes("nonprivate")) <= {"dp.update"}


def test_scope_ms_is_the_union_of_the_scope_ops_per_step(toy):
    w = toy.window
    spans = [(max(o.start, w.start), min(o.end, w.end)) for o in toy.ops
             if S.under(o.scope, "dp.norm/pe") and o.end > w.start
             and o.start < w.end]
    want = 1e3 * T.length(T.union(spans)) / TOY_STEPS
    assert want > 0
    assert toy.scope_ms("dp.norm/pe", TOY_STEPS) == pytest.approx(want)
    both = toy.scope_ms(("dp.noise", "dp.update"), TOY_STEPS)
    assert both <= toy.scope_ms("dp.noise", TOY_STEPS) + \
        toy.scope_ms("dp.update", TOY_STEPS) + 1e-9
    assert toy.scope_ms("dp.norm/gram", TOY_STEPS) is None
    parts = sum(v for k, v in toy.scopes().items())
    # The toy's step is 36 us of device time; the layout copies of its
    # arguments and the key derivation's own small programs, outside
    # every scope, are a few us of it.
    assert 0.8 < toy.coverage() <= 1.0
    assert parts == pytest.approx(toy.coverage() * toy.busy_s, rel=0.05)


def test_scope_readers_on_the_recorded_step(toy):
    ctx = _ctx(toy, TOY_STEPS)
    got = {name: _read(name, ctx) for name in NEW}
    assert got["realize.pe_ms"] == toy.scope_ms("dp.norm/pe", TOY_STEPS)
    assert got["realize.ghost_ms"] == toy.scope_ms("dp.norm/ghost",
                                                   TOY_STEPS)
    assert got["realize.rank1_ms"] == toy.scope_ms("dp.norm/rank1",
                                                   TOY_STEPS)
    assert got["step.capture_ms"] == toy.scope_ms("dp.capture", TOY_STEPS)
    assert got["realize.contrib_ms"] == toy.scope_ms("dp.contrib",
                                                     TOY_STEPS)
    assert got["noise_opt.ms"] == toy.scope_ms(("dp.noise", "dp.update"),
                                               TOY_STEPS)
    assert got["engine.host_ms"] == statistics.median(
        toy.span_ms("engine.private_step"))
    assert got["realize.rank1_ms"] is None
    assert all(v > 0 for k, v in got.items() if k != "realize.rank1_ms")
    # Device time per step is at least the sum of its disjoint phases.
    per_step = 1e3 * toy.busy_s / TOY_STEPS
    assert got["step.capture_ms"] + got["realize.pe_ms"] < per_step


def test_existing_readers_read_the_same_on_the_scoped_trace(toy):
    plain = T.from_profile(str(TOY), 1, frozenset(), TOY_NONPRIVATE)
    for name in EXISTING:
        assert _read(name, _ctx(toy, TOY_STEPS)) == \
            _read(name, _ctx(plain, TOY_STEPS)), name


def test_breakdown_names_scopes_and_program_spans(toy):
    out = toy.breakdown()
    names = [n for n, _ in out["device_ops"]]
    assert names and all(n.startswith(("dp.", "%")) for n in names)
    assert any(n.startswith("dp.norm/pe/conv0 %") for n in names)
    # Idle gaps are labelled by the innermost span open: inside the
    # program's step that is one of the program's spans.
    labels = [label for label, _ in out["idle_gaps"]]
    assert any(label.startswith("engine.") for label in labels), labels
    spans = {s.name for s in toy.spans}
    assert set(labels) <= spans | {"host: no bench span"}, labels


def test_profile_cell_runs_a_cell_and_reads_its_spans(tiny_root):
    """On the CPU the trace has no TPU planes: the listed readers read as
    ``bench/run.py`` does, the device scopes read nothing, and the
    program's host span is read."""
    cell = spec.load_cell("tiny.flat.b8", tiny_root)
    result, out = profile_cell.profile(cell, 2**31 + 11, 0.3,
                                       require_tpu=False, root=tiny_root)
    assert result["correct"], result["checks"]
    assert set(result["metrics"]) == {"loop.host_ms"}
    metrics = out["scope_metrics"]
    assert set(metrics) == set(NEW)
    assert {k for k, v in metrics.items() if v is not None} == \
        {"engine.host_ms"}
    assert 0 < metrics["engine.host_ms"] <= \
        result["metrics"]["loop.host_ms"]["value"]
    assert [r["layer"] for r in out["planner"]] == ["conv0", "conv1", "fc0"]
    assert out["window"]["steps"] >= 2
    json.dumps(out)
