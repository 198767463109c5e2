"""Pallas kernels vs pure-jnp oracles, swept over shapes/dtypes (interpret
mode executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention as attn_ref
from repro.kernels.fused_block.ops import fused_block
from repro.kernels.fused_block.ref import fused_dw_pw
from repro.kernels.int8_gemm.kernel import int8_gemm_pallas
from repro.kernels.int8_gemm.ref import int8_gemm as int8_ref
from repro.quant import quantize


@pytest.mark.parametrize("shape", [(1, 8, 8, 8), (2, 16, 16, 32),
                                   (1, 14, 14, 96), (3, 7, 9, 16)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_block_matches_ref(shape, dtype):
    B, H, W, C = shape
    Co = 2 * C
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], shape, dtype)
    dw_w = (jax.random.normal(ks[1], (3, 3, C)) * 0.3).astype(dtype)
    dw_b = (jax.random.normal(ks[2], (C,)) * 0.1).astype(dtype)
    pw_w = (jax.random.normal(ks[3], (C, Co)) * 0.3).astype(dtype)
    pw_b = (jax.random.normal(ks[4], (Co,)) * 0.1).astype(dtype)
    out = fused_block(x, dw_w, dw_b, pw_w, pw_b)
    ref = fused_dw_pw(x, dw_w, dw_b, pw_w, pw_b)
    tol = 1e-5 if dtype == jnp.float32 else 1.5e-1
    assert out.shape == (B, H, W, Co)
    assert float(jnp.abs(out.astype(jnp.float32)
                         - ref.astype(jnp.float32)).max()) < tol


@pytest.mark.parametrize("mkn", [(128, 64, 128), (256, 128, 256),
                                 (512, 256, 128), (128, 257, 384)])
@pytest.mark.parametrize("per_row", [False, True])
def test_int8_gemm_matches_ref(mkn, per_row):
    """Per-tensor and per-row activation scales: the (tm, 1) row-scale and
    (1, tn) channel-scale blocks must line up with their output tiles."""
    M, K, N = mkn
    a = jax.random.normal(jax.random.PRNGKey(1), (M, K))
    a = a * jnp.linspace(0.1, 4.0, M)[:, None]      # rows of distinct scale
    w = jax.random.normal(jax.random.PRNGKey(2), (K, N))
    aq, asc = quantize(a, axis=0) if per_row else quantize(a)
    wq, wsc = quantize(w, axis=-1)
    out = int8_gemm_pallas(aq, wq, asc, wsc.reshape(-1), tm=128, tn=128,
                           interpret=True)
    ref = int8_ref(aq, wq, asc, wsc.reshape(1, -1))
    assert float(jnp.abs(out - ref).max()) < 1e-4
    # and the whole int8 path stays close to fp32
    rel = float(jnp.abs(out - a @ w).max() / jnp.abs(a @ w).max())
    assert rel < 0.05


@pytest.mark.parametrize("S", [128, 256, 512])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(S, causal, dtype):
    B, H, D = 2, 3, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), dtype)
    k = jax.random.normal(ks[1], (B, H, S, D), dtype)
    v = jax.random.normal(ks[2], (B, H, S, D), dtype)
    out = flash_attention(q, k, v, causal=causal)
    ref = attn_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    assert float(jnp.abs(out.astype(jnp.float32)
                         - ref.astype(jnp.float32)).max()) < tol


def test_flash_attention_agrees_with_model_attention():
    """The Pallas kernel and the model's chunked XLA attention agree — the
    kernel is the TPU serving path for what the dry-run lowers in XLA."""
    from repro.models.lm.attention import gqa_attention
    B, H, S, D = 2, 4, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, H, D))
    v = jax.random.normal(ks[2], (B, S, H, D))
    xla = gqa_attention(q, k, v, causal=True, impl="chunked")
    pal = flash_attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), causal=True)
    err = float(jnp.abs(xla - pal.transpose(0, 2, 1, 3)).max())
    assert err < 2e-5, err
