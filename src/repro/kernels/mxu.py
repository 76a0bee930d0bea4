"""Matrix products inside the Pallas kernels, accumulated in f32."""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax


def _dot(a, b, contract):
    # bf16 products are exact in the MXU's f32 accumulator, and Mosaic
    # refuses a wider contract precision for them — which an enclosing
    # jax.default_matmul_precision("highest") would otherwise request.
    precision = (lax.Precision.DEFAULT
                 if a.dtype == b.dtype == jnp.bfloat16 else None)
    return lax.dot_general(a, b, (contract, ((), ())), precision=precision,
                           preferred_element_type=jnp.float32)


def nn(a, b):
    """a (m, k) · b (k, n) -> (m, n)."""
    return _dot(a, b, ((1,), (0,)))


def nt(a, b):
    """a (m, k) · b (n, k)ᵀ -> (m, n)."""
    return _dot(a, b, ((1,), (1,)))


def tn(a, b):
    """a (k, m)ᵀ · b (k, n) -> (m, n)."""
    return _dot(a, b, ((0,), (0,)))
