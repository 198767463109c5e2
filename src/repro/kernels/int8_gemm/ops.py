"""jit'd wrappers: int8 GEMM for arbitrary shapes (serving path building
block).  ``int8_gemm`` takes pre-quantized operands — the compiled engine
calls it with weights quantized once at compile time; ``int8_matmul`` is the
quantize-on-the-fly convenience wrapper."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.int8_gemm.kernel import int8_gemm_pallas
from repro.kernels.int8_gemm.ref import int8_gemm as int8_gemm_ref
from repro.quant import quantize


def _ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


@partial(jax.jit, static_argnames=("use_pallas", "tm", "tn"))
def int8_gemm(x_q, w_q, x_scale, w_scale, use_pallas: bool = True,
              tm: int = 256, tn: int = 256):
    """x_q (M,K) int8 @ w_q (K,N) int8 -> (M,N) f32 requantized.

    ``x_scale`` is a scalar (per-tensor) or an (M,)/(M,1) per-row vector —
    per-request scales keep batched serving numerics identical to batch-1.
    The Pallas kernel requires M/N to be tile multiples; arbitrary shapes
    are zero-padded up to the tile grid here and the result sliced back.
    """
    M = x_q.shape[0]
    xs = jnp.asarray(x_scale, jnp.float32)
    xs = xs.reshape(()) if xs.size == 1 else xs.reshape(-1, 1)
    if not use_pallas:
        return int8_gemm_ref(x_q, w_q, xs,
                             jnp.asarray(w_scale).reshape(1, -1))
    N = w_q.shape[1]
    tm = min(tm, M)
    tn = min(tn, N)
    mp, np_ = _ceil_to(M, tm), _ceil_to(N, tn)
    xp = jnp.pad(x_q, ((0, mp - M), (0, 0)))
    wp = jnp.pad(w_q, ((0, 0), (0, np_ - N)))
    ws = jnp.pad(jnp.asarray(w_scale, jnp.float32).reshape(1, -1),
                 ((0, 0), (0, np_ - N)))
    xs_rows = jnp.pad(jnp.broadcast_to(xs.reshape(-1, 1), (M, 1)),
                      ((0, mp - M), (0, 0)))
    out = int8_gemm_pallas(xp, wp, xs_rows, ws, tm=tm, tn=tn,
                           interpret=jax.default_backend() == "cpu")
    return out[:M, :N]


@partial(jax.jit, static_argnames=("use_pallas",))
def int8_matmul(x, w, use_pallas: bool = True):
    """f32/bf16 x (M,K) @ w (K,N) through the int8 fixed-point path."""
    x_q, x_s = quantize(x)
    w_q, w_s = quantize(w, axis=-1)
    return int8_gemm(x_q, w_q, x_s, w_s.reshape(-1), use_pallas=use_pallas)
