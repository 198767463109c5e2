"""Pallas TPU kernel: 8-bit fixed-point GEMM (the paper's number format).

MXU-native int8: tiles are (TM x K) x (K x TN) with int32 accumulation and
a fused f32 requantize on the way out.  Tile sizes are multiples of 128 so
the systolic array is fully fed; K stays resident per tile pair (weights
"close to the compute", DHM-style).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _kernel(x_ref, w_ref, xs_ref, ws_ref, out_ref):
    acc = jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out_ref[...] = acc.astype(jnp.float32) * xs_ref[...] * ws_ref[...]


def int8_gemm_pallas(x_q, w_q, x_scale, w_scale, *, tm=256, tn=256,
                     interpret=False):
    """``x_scale`` may be a scalar (per-tensor) or an (M,)/(M,1) per-row
    vector — the serving path quantizes activations per request so batching
    cannot change any request's numerics; each row tile carries its own
    scale slice, mirroring the per-channel ``w_scale`` tile.  Both scales
    travel as 2-D blocks, (tm, 1) rows and (1, tn) channels: Mosaic tiles a
    1-D operand differently from XLA and refuses it."""
    M, K = x_q.shape
    N = w_q.shape[1]
    tm = min(tm, M)
    tn = min(tn, N)
    assert M % tm == 0 and N % tn == 0, (M, N, tm, tn)
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1),
                          (1, N))
    xs = jnp.broadcast_to(jnp.asarray(x_scale, jnp.float32).reshape(-1, 1),
                          (M, 1))
    return pl.pallas_call(
        _kernel,
        grid=(M // tm, N // tn),
        in_specs=[
            pl.BlockSpec((tm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, tn), lambda i, j: (0, j)),
            pl.BlockSpec((tm, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((1, tn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=interpret,
        name="int8_gemm",
    )(x_q, w_q, xs, ws)
