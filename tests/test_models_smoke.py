"""Per-architecture smoke tests (reduced configs, CPU): one DP-ghost train
gradient + prefill + decode step; asserts shapes and finiteness."""
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, PAPER_IDS, get_config
from repro.core import DPConfig
from repro.core.clipping import dp_gradient
from repro.models.registry import build_model

B, T = 2, 16


def make_batch(cfg, rng):
    if cfg.family == "cnn":
        return {"img": jnp.array(rng.randn(B, 3, cfg.img_size, cfg.img_size),
                                 jnp.float32),
                "label": jnp.array(rng.randint(0, cfg.n_classes, (B,)))}
    if cfg.family == "encdec":
        return {"src_frames": jnp.array(rng.randn(B, 8, cfg.d_model),
                                        jnp.float32),
                "tokens": jnp.array(rng.randint(0, cfg.vocab, (B, 8))),
                "labels": jnp.array(rng.randint(0, cfg.vocab, (B, 8)))}
    return {"tokens": jnp.array(rng.randint(0, cfg.vocab, (B, T))),
            "labels": jnp.array(rng.randint(0, cfg.vocab, (B, T)))}


@pytest.mark.parametrize("arch", ARCH_IDS + PAPER_IDS)
def test_arch_smoke(arch):
    cfg = get_config(arch).reduced()
    model = build_model(cfg)
    rng = np.random.RandomState(hash(arch) % 1000)
    params, axes = model.init(jax.random.PRNGKey(0))
    # every param leaf has a logical-axes tuple of matching rank
    for (kp, leaf), (_, ax) in zip(
            jax.tree_util.tree_leaves_with_path(params),
            jax.tree_util.tree_leaves_with_path(
                axes, is_leaf=lambda x: isinstance(x, tuple))):
        assert len(ax) == leaf.ndim, (jax.tree_util.keystr(kp), ax)

    batch = make_batch(cfg, rng)
    loss, grad, aux = dp_gradient(
        model.apply, params, batch,
        cfg=DPConfig(l2_clip=1.0, noise_multiplier=0.0),
        key=jax.random.PRNGKey(1))
    assert np.isfinite(float(loss))
    assert all(bool(jnp.isfinite(g).all()) for g in jax.tree.leaves(grad))
    norms = aux["per_example_norms"]
    assert norms.shape == (B,) and bool(jnp.all(norms > 0))

    if cfg.family == "cnn":
        return
    if cfg.family == "encdec":
        logits, cache = model.prefill(params, batch["src_frames"],
                                      batch["tokens"], max_len=32)
    else:
        logits, cache = model.prefill(params, batch["tokens"], max_len=32)
    assert logits.shape == (B, cfg.padded_vocab)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache = model.decode_step(params, cache, tok)
    assert logits2.shape == (B, cfg.padded_vocab)
    assert bool(jnp.isfinite(logits2).all())
    assert int(cache["pos"]) > 0


@pytest.mark.parametrize("arch,over,count", [
    ("alexnet", {}, 74_732_328),
    ("alexnet", {"cnn_avgpool": 6}, 61_100_840),   # torchvision's alexnet
    ("vgg16", {}, 169_814_824),
], ids=["alexnet", "alexnet_avgpool6", "vgg16"])
def test_cnn_parameter_count_at_256px(arch, over, count):
    model = build_model(get_config(arch).replace(**over))
    shapes = jax.eval_shape(lambda k: model.init(k)[0], jax.random.PRNGKey(0))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == count


@pytest.mark.parametrize("n,out", [(7, 6), (4, 3), (6, 6), (5, 2)])
def test_adaptive_avgpool_takes_torchvisions_windows(n, out):
    from repro.models.cnn import _adaptive_avgpool
    x = np.random.RandomState(n * 10 + out).randn(2, 3, n, n)
    cut = [(math.floor(i * n / out), math.ceil((i + 1) * n / out))
           for i in range(out)]
    want = np.stack([np.stack([x[:, :, a:b, c:d].mean((2, 3))
                               for c, d in cut], -1) for a, b in cut], -2)
    got = _adaptive_avgpool(jnp.asarray(x, jnp.float32), out)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
