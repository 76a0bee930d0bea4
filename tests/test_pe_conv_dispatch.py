"""Which implementation forms a convolution's per-example weight gradients.

``conv_impl="auto"`` (NormCfg's default) takes the ``pe_conv_grad`` MXU
kernel on a TPU for plain convolutions whose input has at least
``convops.MXU_MIN_CHANNELS`` channels, one batched dot per kernel tap
(``taps``) for narrower plain ones, either of them after space to depth
for strided rank-1 and rank-2 ones (``s2d_pallas``, ``s2d_taps``), and
the grouped-convolution lowering (``fgc``) everywhere else.  The choice is read from
``tapper.STATS.conv_impls``, tallied as the step is traced; here it is
traced with ``jax.eval_shape``, so a TPU branch can be taken on the CPU.
The kernel path itself runs in interpret mode against ``fgc`` through the
planned pipeline under every clipping mode.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import ClipPolicy, NormCfg, clipped_grad_sum_detailed
from repro.core.tapper import STATS
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.models import convops

F32 = jnp.float32


def _tally(x_shape, dy_shape, *, impl="auto", kernel=(3, 3), **conv):
    STATS.reset()
    jax.eval_shape(
        lambda x, dy: convops.pe_conv_grad(x, dy, kernel_spatial=kernel,
                                           impl=impl, **conv),
        jax.ShapeDtypeStruct(x_shape, F32),
        jax.ShapeDtypeStruct(dy_shape, F32))
    return dict(STATS.conv_impls)


@pytest.fixture
def tpu(monkeypatch):
    monkeypatch.setattr(kops, "on_tpu", lambda: True)


def test_auto_takes_the_kernel_for_a_plain_conv_on_tpu(tpu):
    # VGG16 conv1's shape at a small image: 3x3, stride 1, padding 1
    assert _tally((4, 64, 16, 16), (4, 64, 16, 16), padding=1) == \
        {"pallas": 1}
    # a rank-1 conv (stride 1, valid) goes the same way
    assert _tally((4, 64, 20), (4, 32, 18), kernel=(3,)) == {"pallas": 1}


@pytest.mark.parametrize("case", [
    # AlexNet conv0's stride and kernel, grouped: space to depth is
    # for ungrouped convolutions only
    dict(x=(2, 64, 35, 35), dy=(2, 64, 8, 8), kernel=(11, 11), stride=4,
         padding=2, groups=2),
    dict(x=(2, 64, 12, 12), dy=(2, 64, 8, 8), dilation=2),
    dict(x=(2, 64, 12, 12), dy=(2, 64, 10, 10), groups=2),
    # padding as wide as the kernel: the kernel reads unpadded captures
    dict(x=(2, 64, 8, 8), dy=(2, 64, 12, 12), padding=3),
    # a narrow rank-3 input with stride 2: space to depth is for rank 1, 2
    dict(x=(2, 3, 9, 9, 9), dy=(2, 8, 4, 4, 4), kernel=(3, 3, 3), stride=2),
], ids=["stride4", "dilated", "grouped", "wide_padding", "narrow_strided"])
def test_auto_keeps_fgc_where_the_kernel_does_not_apply(tpu, case):
    case = dict(case)
    x, dy = case.pop("x"), case.pop("dy")
    assert _tally(x, dy, **case) == {"fgc": 1}


@pytest.mark.parametrize("case", [
    # VGG16 conv0's shape at a small image: an RGB input, 3x3, padding 1
    dict(x=(3, 3, 12, 12), dy=(3, 8, 12, 12), padding=1),
    dict(x=(2, 5, 9, 7), dy=(2, 4, 9, 7), kernel=(5, 5), padding=2),
    dict(x=(2, 8, 20), dy=(2, 6, 18), kernel=(3,)),
], ids=["rgb_3x3", "5x5", "rank1"])
def test_auto_takes_per_tap_dots_for_narrow_inputs_on_tpu(tpu, case):
    """Below MXU_MIN_CHANNELS input channels a plain convolution takes one
    batched dot per kernel tap, which matches fgc (plain XLA, so it runs
    on the CPU as well)."""
    case = dict(case)
    x_shape, dy_shape = case.pop("x"), case.pop("dy")
    kernel = case.pop("kernel", (3, 3))
    assert _tally(x_shape, dy_shape, kernel=kernel, **case) == {"taps": 1}
    rng = np.random.RandomState(len(x_shape) + x_shape[1])
    x = jnp.asarray(rng.randn(*x_shape), F32)
    dy = jnp.asarray(rng.randn(*dy_shape), F32)
    got = convops.pe_conv_grad(x, dy, kernel_spatial=kernel, **case)
    want = convops.pe_conv_grad(x, dy, kernel_spatial=kernel, impl="fgc",
                                **case)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


def test_auto_is_fgc_on_the_cpu():
    assert not kops.on_tpu()
    assert _tally((4, 64, 16, 16), (4, 64, 16, 16), padding=1) == {"fgc": 1}
    assert _tally((4, 3, 16, 16), (4, 64, 16, 16), padding=1) == {"fgc": 1}
    assert _tally((2, 3, 35, 35), (2, 64, 8, 8), kernel=(11, 11), stride=4,
                  padding=2) == {"fgc": 1}


@pytest.mark.parametrize("impl", ["fgc", "bgc", "pallas"])
def test_explicit_impls_are_honoured(tpu, impl):
    # explicit pallas takes the kernel below 64 channels too
    assert _tally((4, 8, 16, 16), (4, 16, 16, 16), impl=impl,
                  padding=1) == {impl: 1}


def test_explicit_pallas_falls_back_to_fgc_for_a_strided_conv(tpu):
    # a strided dilated conv: neither the kernel nor space to depth
    assert _tally((2, 64, 17, 17), (2, 64, 7, 7), impl="pallas",
                  stride=2, dilation=2) == {"fgc": 1}
    # a plain strided one takes the kernel after space to depth
    assert _tally((2, 64, 17, 17), (2, 64, 8, 8), impl="pallas",
                  stride=2) == {"s2d_pallas": 1}


@pytest.mark.parametrize("case", [
    # AlexNet conv0: 11x11, stride 4 -> 16·3 = 48 channels
    dict(x=(2, 3, 35, 35), dy=(2, 64, 8, 8), kernel=(11, 11), stride=4,
         padding=2, want="s2d_taps"),
    # stride 2 on 16 channels -> 64, the kernel's channel floor
    dict(x=(2, 16, 17, 17), dy=(2, 32, 8, 8), stride=2, want="s2d_pallas"),
    dict(x=(2, 8, 21), dy=(2, 8, 7), kernel=(5,), stride=3, padding=1,
         want="s2d_taps"),
], ids=["alexnet_conv0", "stride2_64ch", "rank1"])
def test_auto_takes_space_to_depth_for_strided_convs_on_tpu(tpu, case):
    case = dict(case)
    x, dy, want = case.pop("x"), case.pop("dy"), case.pop("want")
    assert _tally(x, dy, **case) == {want: 1}


def _strided_oracle(x, dy, kernel, stride, padding):
    """``kernels/ref.py``'s stride-1 oracle on the padded input against δy
    spread out by the stride (zeros between its positions)."""
    (KH, KW), s, p = kernel, stride, padding
    B, D, Ho, Wo = dy.shape
    dyd = jnp.zeros((B, D, s * (Ho - 1) + 1, s * (Wo - 1) + 1), F32)
    dyd = dyd.at[:, :, ::s, ::s].set(dy)
    xp = jnp.pad(x, ((0, 0), (0, 0), (p, p), (p, p)))
    xp = xp[:, :, :dyd.shape[2] + KH - 1, :dyd.shape[3] + KW - 1]
    return ref.pe_conv_grad_2d_ref(xp, dyd, KH, KW)


@pytest.mark.parametrize("inner", ["taps", "pallas"])
@pytest.mark.parametrize("case", [
    # AlexNet conv0's geometry at a small image
    dict(x=(2, 3, 35, 35), kernel=(11, 11), stride=4, padding=2),
    dict(x=(2, 3, 17, 17), kernel=(3, 3), stride=2, padding=0),
    dict(x=(2, 3, 17, 17), kernel=(3, 3), stride=2, padding=1),
    dict(x=(2, 3, 16, 16), kernel=(4, 4), stride=2, padding=0),
    dict(x=(2, 3, 16, 16), kernel=(4, 4), stride=2, padding=1),
], ids=["alexnet_conv0", "odd_k_pad0", "odd_k_pad1", "even_k_pad0",
        "even_k_pad1"])
def test_space_to_depth_matches_fgc_and_the_oracle(monkeypatch, case, inner):
    """Both inner routes: ``taps`` as ``auto`` takes it on a TPU below the
    kernel's channel floor, ``pallas`` as an explicit ``pallas`` takes it
    (the kernel in interpret mode here); each tallied under its key."""
    kernel, stride, padding = case["kernel"], case["stride"], case["padding"]
    out = convops.conv_output_spatial(case["x"][2:], kernel, stride, 1,
                                      padding)
    rng = np.random.RandomState(sum(kernel) + padding)
    x = jnp.asarray(rng.randn(*case["x"]), F32)
    dy = jnp.asarray(rng.randn(case["x"][0], 6, *out), F32)
    conv = dict(kernel_spatial=kernel, stride=stride, padding=padding)
    if inner == "taps":
        monkeypatch.setattr(kops, "on_tpu", lambda: True)
    STATS.reset()
    got = convops.pe_conv_grad(x, dy, impl="auto" if inner == "taps"
                               else "pallas", **conv)
    assert dict(STATS.conv_impls) == {f"s2d_{inner}": 1}
    want = convops.pe_conv_grad(x, dy, impl="fgc", **conv)
    assert got.shape == want.shape == (2, 6, 3) + kernel
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(_strided_oracle(x, dy, kernel, stride,
                                                    padding)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", [
    dict(kernel=(11, 11), stride=4, taps=144, route="s2d_taps"),
    dict(kernel=(3, 3), stride=2, taps=16, route="s2d_taps"),
    dict(kernel=(4, 4), stride=2, taps=16, route="s2d_taps"),
    dict(kernel=(3, 3), stride=1, taps=9, route="taps"),
    dict(kernel=(3, 3), stride=2, dilation=2, taps=9, route="fgc"),
    dict(kernel=(11, 11), stride=4, impl="fgc", taps=121, route="fgc"),
], ids=["alexnet_conv0", "odd_k", "even_k", "stride1", "dilated",
        "alexnet_conv0_fgc"])
def test_pe_kernel_taps_counts_the_padded_taps(tpu, case):
    """The route of a 3-channel conv on a TPU, and the taps it computes."""
    case = dict(case)
    taps, kernel, route = case.pop("taps"), case.pop("kernel"), \
        case.pop("route")
    assert convops.pe_conv_route(kernel, 3, **case) == route
    assert convops.route_taps(route, kernel, case.get("stride", 1)) == taps


def test_kernel_operands_are_bf16_at_default_precision(tpu):
    """On the TPU at the default matmul precision the kernel reads bf16
    operands, which is the rounding one bf16 MXU pass applies; under
    ``highest`` it reads f32."""
    def kernel_operand_dtypes():
        jaxpr = jax.make_jaxpr(lambda x, dy: convops.pe_conv_grad(
            x, dy, kernel_spatial=(3, 3), padding=1, impl="auto"))(
            jnp.zeros((2, 64, 8, 8), F32), jnp.zeros((2, 64, 8, 8), F32))
        found = []

        def walk(jx):
            for eqn in jx.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.extend(str(v.aval.dtype) for v in eqn.invars)
                for v in eqn.params.values():
                    if isinstance(v, jax.extend.core.ClosedJaxpr):
                        walk(v.jaxpr)
        walk(jaxpr.jaxpr)
        return found

    assert kernel_operand_dtypes() == ["bfloat16", "bfloat16"]
    with jax.default_matmul_precision("highest"):
        assert kernel_operand_dtypes() == ["float32", "float32"]


def test_norm_cfg_defaults_to_auto():
    assert NormCfg().conv_impl == "auto"


# ---------------------------------------------------------------------------
# The kernel path (interpret mode) against fgc through the planned pipeline


def _toy_cnn(B, seed=0):
    """Two 3x3 padding-1 convs and a dense head; every conv realizes its
    norm per example (``conv_norm="pe"``), so its gradient is stashed."""
    rng = np.random.RandomState(seed)
    params = {"c0": {"w": jnp.asarray(rng.randn(8, 4, 3, 3), F32) * 0.3,
                     "b": jnp.asarray(rng.randn(8), F32) * 0.1},
              "c1": {"w": jnp.asarray(rng.randn(8, 8, 3, 3), F32) * 0.2},
              "fc": {"w": jnp.asarray(rng.randn(8, 3), F32) * 0.3}}

    def apply_fn(p, batch, tp):
        h = tp.conv("c0", batch["x"], p["c0"]["w"], p["c0"]["b"], padding=1)
        h = jax.nn.relu(h)
        h = tp.conv("c1", h, p["c1"]["w"], padding=1)
        o = tp.dense("fc", jnp.tanh(h).mean(axis=(2, 3)), p["fc"]["w"])
        return jnp.sum(o ** 2, axis=1)

    return apply_fn, params, {"x": jnp.asarray(rng.randn(B, 4, 6, 6), F32)}


@pytest.mark.parametrize("B", [8, 5], ids=["full_block", "partial_block"])
@pytest.mark.parametrize("mode", ["flat", "per_layer", "stale"])
def test_kernel_path_matches_fgc_in_the_planned_pipeline(mode, B):
    apply_fn, params, batch = _toy_cnn(B)
    kw = dict(l2_clip=0.5, strategy="auto", conv_norm="pe",
              clip_policy=ClipPolicy(mode=mode))
    if mode == "stale":
        kw["prev_norms_sq"] = jnp.linspace(0.1, 2.0, B, dtype=F32)
    STATS.reset()
    _, g_ref, n_ref, _ = clipped_grad_sum_detailed(
        apply_fn, params, batch, conv_impl="fgc", **kw)
    assert STATS.conv_impls["fgc"] == 2
    STATS.reset()
    _, g_mxu, n_mxu, _ = clipped_grad_sum_detailed(
        apply_fn, params, batch, conv_impl="pallas", **kw)
    assert dict(STATS.conv_impls) == {"pallas": 2}
    np.testing.assert_allclose(np.asarray(n_mxu), np.asarray(n_ref),
                               rtol=2e-5)
    for a, b in zip(jax.tree.leaves(g_mxu), jax.tree.leaves(g_ref)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-6)


@pytest.mark.parametrize("on_mesh", [False, True], ids=["no_mesh", "mesh"])
def test_engine_runs_the_kernel_per_device_under_its_mesh(on_mesh):
    """The SPMD partitioner cannot split a Pallas kernel, so the engine
    traces its step under its own mesh and the kernel wrapper runs the
    kernel on each device's examples in a ``shard_map`` over the data
    axes; the step equals the ``fgc`` one (a one-device mesh here)."""
    from repro.core import DPConfig, PrivacyEngine
    from repro.launch.mesh import make_auto_mesh
    from repro.optim import adamw_init
    apply_fn, params, batch = _toy_cnn(8)
    mesh = make_auto_mesh((1,), ("data",)) if on_mesh else None
    key = jax.random.key_data(jax.random.PRNGKey(3))
    out = {}
    for impl in ("fgc", "pallas"):
        eng = PrivacyEngine(
            apply_fn, params, batch, lr=1e-2, mesh=mesh,
            dp=DPConfig(l2_clip=0.5, noise_multiplier=1.0,
                        norm=NormCfg(conv="pe", conv_impl=impl)))
        out[impl] = eng.private_step(params, adamw_init(params), batch, key)
        with eng._mesh_context():
            jaxpr = jax.make_jaxpr(eng._step_fn())(
                params, adamw_init(params), batch, key, {})
        assert ("shard_map" in str(jaxpr)) == (on_mesh and impl == "pallas")
    for a, b in zip(jax.tree.leaves(out["pallas"][0]),
                    jax.tree.leaves(out["fgc"][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-5,
                                   atol=1e-6)
