"""The ``pe_conv_grad`` kernel's work counted from its operation's name,
and the roofline share read from it, on hand-made trace events in the two
forms a TPU writes the call in."""
import types

import pytest

from bench import spec
from bench import trace as T
from bench.flops import pe_conv_grad as pc

# AlexNet conv0 through space to depth at 256 per chip: 48 channels on a
# 65x65 grid, 3x3 taps; operand shapes in the call's layout constraints.
CONV0 = ("%pe_conv_grad.1 = f32[256,9,48,64]{3,2,1,0:T(8,128)} custom-call("
         "%bitcast.2, %bitcast.3), custom_call_target=\"tpu_custom_call\", "
         "operand_layout_constraints={bf16[65,65,256,48]{3,2,1,0}, "
         "bf16[63,63,256,64]{3,2,1,0}}, metadata={op_name=\"jit(step)/"
         "pe_conv_grad/pallas_call\"}, backend_config={\"custom_call_config"
         "\":{\"body\":\"TUzvUgFNTElS\"}}")
# VGG16 conv1 at batch 32, operand shapes beside the operands.
CONV1 = ("%pe_conv_grad.9 = f32[32,9,64,64]{3,2,1,0:T(8,128)} custom-call("
         "bf16[256,256,32,64]{3,2,1,0:T(16,128)(2,1)} %copy.1, "
         "bf16[256,256,32,64]{3,2,1,0:T(16,128)(2,1)} %copy.2), "
         "custom_call_target=\"tpu_custom_call\"")
PEAKS = spec.peaks("TPU v5 lite")


def test_space_to_depth_conv0_counts_its_padded_taps():
    call = pc.parse(CONV0)
    assert call.x == (65, 65, 256, 48) and call.dy == (63, 63, 256, 64)
    # 2·B·T·C·K·D with 144 taps of the 11x11 kernel over 3 channels
    assert call.flops == 2.0 * 256 * 63 * 63 * 3 * 144 * 64
    assert call.flops == pytest.approx(56.19e9, rel=1e-3)
    assert call.hbm_bytes == (2 * (65 * 65 + 63 * 63) * 256 * 48
                              + 2 * 63 * 63 * 256 * 16
                              + 4 * 256 * 9 * 48 * 64)


def test_operand_shapes_beside_the_operands():
    call = pc.parse(CONV1)
    assert call.flops == 2.0 * 32 * 256 * 256 * 64 * 9 * 64
    assert call.hbm_bytes == 2 * 2 * 256 * 256 * 32 * 64 + 4 * 32 * 9 * 64 \
        * 64


@pytest.mark.parametrize("text", [
    "%fusion.3 = f32[32,9,64,64]{3,2,1,0} fusion(f32[2]{0} %a)",
    "%pe_conv_grad.1 = f32[256,9,48,64]{3,2,1,0} custom-call(%a, %b)",
    "jit_step(1234)",
])
def test_other_operations_are_not_calls(text):
    assert pc.parse(text) is None


def _ctx(ops, peaks=PEAKS):
    tr = T.Trace(ops=ops, spans=[T.Span("bench.window", 0.0, 10.0)],
                 n_devices=2, nonprivate_steps=0)
    return types.SimpleNamespace(trace=tr, peaks=peaks)


def test_roofline_share_over_the_calls_in_the_window():
    read = spec.load_reader("kernel.pe_conv_grad_roofline").read
    c0, c1 = pc.parse(CONV0), pc.parse(CONV1)
    t0 = max(c0.flops / PEAKS["bf16_flops_per_s"],
             c0.hbm_bytes / PEAKS["hbm_bytes_per_s"])
    t1 = max(c1.flops / PEAKS["bf16_flops_per_s"],
             c1.hbm_bytes / PEAKS["hbm_bytes_per_s"])
    ops = [T.Op(0, CONV0, 1.0, 1.0 + 4 * t0),       # at 25% of its roofline
           T.Op(1, CONV0, 2.0, 2.0 + 4 * t0),
           T.Op(0, CONV1, 3.0, 3.0 + 2 * t1),       # at 50%
           T.Op(0, "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %x)", 0, 9),
           T.Op(1, CONV1, 10.0 - t1, 10.0 + t1)]    # half past the window
    want = (2 * t0 + t1 + t1 / 2) / (8 * t0 + 2 * t1 + t1)
    assert read(_ctx(ops)) == pytest.approx(100 * want)
    assert 0 < read(_ctx(ops)) < 100


def test_nothing_to_read_without_calls_or_peaks():
    read = spec.load_reader("kernel.pe_conv_grad_roofline").read
    assert read(_ctx([T.Op(0, "%fusion.1 = f32[8]{0} fusion()", 0, 1)])) \
        is None
    assert read(_ctx([T.Op(0, CONV1, 0, 1)], peaks={})) is None
