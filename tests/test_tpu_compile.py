"""Every Pallas kernel compiled ahead of time for a TPU v5e chip, at the
widths the main path feeds it.  Interpret mode (tests/test_kernels.py)
checks the kernels' arithmetic; only the TPU compiler checks their block
shapes, layouts and VMEM use, and it runs here without a chip: the chip is
described (``v5e:2x2``), not attached."""
import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import flash_attn, gram_norm, ops
from repro.kernels import pe_conv_grad as pc

F32, BF16 = jnp.float32, jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    """One described v5e chip, with the persistent compilation cache off
    while this module compiles for it (a TPU executable written to the
    cache cannot be read back on the CPU)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


# VGG16 at 3x256x256, batch 16: the patch matrices its conv layers feed the
# Gram kernels (T = out_h * out_w, Di = C * 9, Do = D) and fc0.
VGG_DENSE = {
    "conv8": (16, 32 * 32, 512 * 9, 512),
    "conv10": (16, 16 * 16, 512 * 9, 512),
    "fc0": (16, 1, 512 * 8 * 8, 4096),
}


@pytest.mark.parametrize("layer", sorted(VGG_DENSE))
@pytest.mark.parametrize("has_bias", [False, True])
def test_gram_norm_fused_compiles(one_chip, layer, has_bias):
    B, T, Di, Do = VGG_DENSE[layer]
    _compile(lambda x, dy, w: gram_norm.gram_norm_fused(
        x, dy, w, has_bias=has_bias, interpret=False),
        [((B, T, Di), F32), ((B, T, Do), F32), ((B,), F32)], one_chip)


@pytest.mark.parametrize("layer", sorted(VGG_DENSE))
def test_gram_norm_compiles(one_chip, layer):
    B, T, Di, Do = VGG_DENSE[layer]
    _compile(lambda x, dy: gram_norm.gram_norm(x, dy, has_bias=True,
                                               interpret=False),
             [((B, T, Di), F32), ((B, T, Do), F32)], one_chip)


# (C, D, image side) of VGG16's 3x3, padding-1 convs at 256 px, at the
# batch of the vgg16.flat.b32 cell, with the row tile the wrapper picks.
VGG_CONV = {"conv1": (64, 64, 256), "conv3": (128, 128, 128),
            "conv8": (512, 512, 32)}


@pytest.mark.parametrize("layer", sorted(VGG_CONV))
@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_pe_conv_grad_2d_compiles(one_chip, layer, dtype):
    """bf16 is what the wrapper hands the kernel at the default matmul
    precision; f32 under ``highest``."""
    C, D, S = VGG_CONV[layer]
    B = 32
    th = pc.row_tile(S, 3, 3, S, ops.VMEM_BUDGET,
                     pc.examples_per_step(dtype))
    _compile(lambda x, dy: pc.pe_conv_grad_2d(
        x, dy, KH=3, KW=3, padding=(1, 1), th=th, interpret=False),
        [((B, C, S, S), dtype), ((B, D, S, S), dtype)], one_chip)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["f32", "bf16"])
def test_pe_conv_grad_2d_compiles_alexnet_conv1(one_chip, dtype):
    """AlexNet conv1 (5x5, padding 2, 64 -> 192 on 31x31) at the batch of
    the alexnet.flat.b256 cell, with the row tile the wrapper picks: 31
    rows in two tiles of 16, the last one row past the image."""
    th = pc.row_tile(31, 5, 5, 31, ops.VMEM_BUDGET,
                     pc.examples_per_step(dtype))
    assert 31 % th
    _compile(lambda x, dy: pc.pe_conv_grad_2d(
        x, dy, KH=5, KW=5, padding=(2, 2), th=th, interpret=False),
        [((256, 64, 31, 31), dtype), ((256, 192, 31, 31), dtype)], one_chip)


@pytest.mark.parametrize("inner", ["taps", "pallas"])
def test_space_to_depth_conv0_compiles(one_chip, monkeypatch, inner):
    """AlexNet conv0 (11x11, stride 4, padding 2, 3 -> 64 at 256 px) at its
    per-chip batch of the four-chip cell, through space to depth: 48
    channels on a 65x65 grid, 3x3 taps, by per-tap dots or the kernel."""
    from repro.models import convops
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    B = 256
    args = [jax.ShapeDtypeStruct(s, F32, sharding=one_chip)
            for s in ((B, 3, 256, 256), (B, 64, 63, 63))]
    compiled = jax.jit(lambda x, dy: convops._pe_conv_grad_s2d(
        x, dy, (11, 11), 4, 2, inner)).lower(*args).compile()
    assert ("tpu_custom_call" in compiled.as_text()) == (inner == "pallas")


def test_pe_conv_grad_compiles_on_a_data_mesh(one_chip, monkeypatch):
    """AlexNet conv1 (5x5, 64 -> 192 on 31x31) at the four-chip cell's
    global batch of 1024, split over a data:4 mesh of the described host:
    the partitioner cannot split a Pallas kernel, so under the mesh the
    wrapper runs it per device in a shard_map (``ops.per_example``)."""
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_auto_mesh
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = make_auto_mesh((4,), ("data",), devices=topo.devices)
    rows = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct(s, F32, sharding=rows)
            for s in ((1024, 64, 31, 31), (1024, 192, 31, 31))]
    with jax.set_mesh(mesh):
        compiled = jax.jit(lambda x, dy: ops.pe_conv_grad(
            x, dy, kernel_spatial=(5, 5), padding=2)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pe_conv_grad_1d_compiles(one_chip):
    B, C, D, T, K = 16, 256, 256, 1026, 3
    th = pc.row_tile(T, K, 1, 1, ops.VMEM_BUDGET)
    _compile(lambda x, dy: pc.pe_conv_grad_1d(x, dy, K=K, th=th,
                                              interpret=False),
             [((B, C, T), F32), ((B, D, T - K + 1), F32)], one_chip)


@pytest.mark.parametrize("precision", ["default", "highest"])
def test_flash_attention_fwd_bwd_compiles(one_chip, precision):
    """bf16, T = 4096, head_dim 128, 4 query heads per kv head: the
    forward kernel and both backward kernels, also inside
    ``jax.default_matmul_precision("highest")`` (Mosaic refuses an fp32
    contract precision on bf16 operands)."""
    B, T, H, Hkv, hd = 1, 4096, 8, 2, 128

    def loss(q, k, v):
        o = flash_attn.flash_attention(q, k, v, causal=True, interpret=False)
        return jnp.sum(o.astype(F32))

    with jax.default_matmul_precision(precision):
        compiled = _compile(jax.grad(loss, argnums=(0, 1, 2)),
                            [((B, T, H, hd), BF16), ((B, T, Hkv, hd), BF16),
                             ((B, T, Hkv, hd), BF16)], one_chip)
    assert compiled.as_text().count("tpu_custom_call") >= 3


def test_bf16_kernels_compile_at_highest_precision(one_chip):
    """The Gram and conv kernels on bf16 captures inside
    ``jax.default_matmul_precision("highest")``."""
    B, T, Di, Do = VGG_DENSE["conv10"]
    C, D, S = VGG_CONV["conv8"]
    with jax.default_matmul_precision("highest"):
        _compile(lambda x, dy, w: gram_norm.gram_norm_fused(
            x, dy, w, has_bias=True, interpret=False),
            [((B, T, Di), BF16), ((B, T, Do), BF16), ((B,), F32)], one_chip)
        _compile(lambda x, dy: gram_norm.gram_norm(x, dy, interpret=False),
                 [((B, T, Di), BF16), ((B, T, Do), BF16)], one_chip)
        _compile(lambda x, dy: pc.pe_conv_grad_2d(
            x, dy, KH=3, KW=3, padding=(1, 1), th=8, interpret=False),
            [((B, C, S, S), BF16), ((B, D, S, S), BF16)], one_chip)
