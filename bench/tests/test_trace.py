"""The reduction from a profiler trace to per-layer numbers, on a small
trace recorded on one TPU v5e chip and on hand-made events.

``data/tiny.xplane.pb`` holds three runs of one small jitted program
(a grouped convolution, a dense convolution and a matrix product), each
inside host spans ``bench.dispatch`` and ``bench.wait``."""
import pathlib

import pytest

from bench import trace as T

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_interval_arithmetic():
    assert T.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5),
                                                             (3, 4)]
    assert T.length([(0, 2.5), (3, 4)]) == 3.5
    assert T.clip([(0, 2), (3, 4)], 1, 3.5) == [(1, 2), (3, 3.5)]
    assert T.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == \
        [(0, 1), (2, 4), (6, 9)]
    assert T.subtract([(0, 1), (5, 6)], []) == [(0, 1), (5, 6)]


def test_op_names_parse_to_results_and_opcode():
    name, sizes, op = T.parse_op(
        "%multiply_reduce_fusion.3 = (f32[16384]{0:T(1024)S(1)}, "
        "f32[1,16384,512,3,3]{1,0,4,3,2:T(1,128)}) fusion(bf16[1,32,512,32,"
        "32]{1,0,4,3,2:T(2,128)(2,1)} %copy.535), kind=kOutput, "
        "calls=%fused_computation.3")
    assert (name, sizes, op) == ("multiply_reduce_fusion.3",
                                 [16384, 16384 * 512 * 9], "fusion")
    name, sizes, op = T.parse_op(
        "%all-reduce.7 = f32[4096,1000]{1,0:T(8,128)} all-reduce("
        "f32[4096,1000]{1,0:T(8,128)} %fusion.3), channel_id=1, "
        "replica_groups=[1,4]<=[4], to_apply=%add")
    assert (sizes, op) == ([4096 * 1000], "all-reduce")
    assert T.parse_op("jit_step(1234)") == ("jit_step(1234)", [], None)


def test_tags():
    pe = frozenset({32 * 512 * 512 * 9})
    conv = ("%multiply_reduce_fusion.3 = (f32[16384]{0}, "
            "f32[1,16384,512,3,3]{1,0,4,3,2}) fusion(bf16[2]{0} %c)")
    assert T.tag_op(conv, pe) == {"pe_conv"}
    assert T.tag_op(conv, frozenset({7})) == frozenset()
    for text in ("%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %x)",
                 "%all-reduce-start = f32[8]{0} all-reduce-start(f32[8] %x)",
                 "%all-gather-done.2 = f32[8]{0} all-gather-done(f32[8] %x)",
                 "%all-reduce.3 = f32[8]{0} fusion(f32[8]{0} %x), "
                 "kind=kLoop"):
        assert T.tag_op(text, pe) == {"collective"}, text
    assert T.tag_op("%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", pe) == \
        frozenset()


def _hand_trace():
    """Two devices over a window of 10 s.  Device 0: compute 0-4, an
    all-reduce 3-6 (exposed 4-6), compute 8-9.  Device 1: compute 0-5,
    an all-reduce 5-7 (all exposed)."""
    ops = [T.Op(0, "c0", 0, 4, frozenset({"pe_conv"})),
           T.Op(0, "ar0", 3, 6, frozenset({"collective"})),
           T.Op(0, "c1", 8, 9),
           T.Op(1, "c2", 0, 5),
           T.Op(1, "ar1", 5, 7, frozenset({"collective"})),
           T.Op(0, "np", 11, 11.5), T.Op(1, "np", 11, 11.5)]
    spans = [T.Span("bench.window", 0, 10), T.Span("bench.wait", 6, 8),
             T.Span("bench.nonprivate", 10.5, 12)]
    return T.Trace(ops=ops, spans=spans, n_devices=2, nonprivate_steps=1)


def test_busy_idle_and_exposed_collectives_by_hand():
    tr = _hand_trace()
    assert tr.window_s == 10
    assert tr.busy_s == pytest.approx((7 + 7) / 2)
    assert tr.exposed_collective_share() == pytest.approx((2 + 2) / 2 / 10)
    assert tr.share_of("private", "pe_conv") == pytest.approx(4 / 14)
    assert tr.busy_per_run("private", 2) == pytest.approx(3.5)
    assert tr.busy_per_run("nonprivate", 1) == pytest.approx(0.5)
    gaps = tr.breakdown()["idle_gaps"]
    assert gaps[0] == ["bench.wait", pytest.approx(2.0)]
    assert gaps[1] == ["host: no bench span", pytest.approx(1.0)]


def test_without_collectives_there_is_nothing_to_read():
    tr = _hand_trace()
    tr = T.Trace(ops=[o for o in tr.ops if "collective" not in o.tags],
                 spans=tr.spans, n_devices=2, nonprivate_steps=1)
    assert tr.exposed_collective_share() is None
    tr = T.Trace(ops=[o for o in tr.ops if "pe_conv" not in o.tags],
                 spans=tr.spans, n_devices=2, nonprivate_steps=1)
    assert tr.share_of("private", "pe_conv") is None


def test_recorded_trace():
    # Tag operations whose result holds 2048 elements, as many as the
    # grouped convolution's input, which the program copies to bf16.
    tr = T.from_profile(str(DATA / "tiny.xplane.pb"), 1, frozenset({2048}),
                        0)
    waits = [s for s in tr.spans if s.name == "bench.wait"]
    dispatches = [s for s in tr.spans if s.name == "bench.dispatch"]
    assert len(waits) == len(dispatches) == 3
    assert {o.device for o in tr.ops} == {0}
    tr.spans.append(T.Span("bench.window", dispatches[0].start,
                           waits[-1].end))
    # Busy time is the union of the operations, which here run one at a
    # time: the sum of their durations.
    ops = sorted(tr.ops, key=lambda o: o.start)
    assert all(a.end <= b.start for a, b in zip(ops, ops[1:]))
    assert tr.busy_s == pytest.approx(sum(o.end - o.start for o in ops))
    assert 0 < tr.busy_s < tr.window_s
    idle = 1 - tr.busy_s / tr.window_s
    assert 0.9 < idle < 1.0     # three ~3 us programs in ~1.3 ms of host
    tagged = [o for o in tr.ops if "pe_conv" in o.tags]
    assert tagged and all("bf16[1,8,16,16]" in o.name for o in tagged)
    assert tr.exposed_collective_share() is None
    names = [n for n, _ in tr.breakdown()["device_ops"]]
    assert names and all(n.startswith("%") for n in names)
