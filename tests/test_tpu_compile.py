"""Compile the served Pallas kernels for a TPU v5e that is described, not
attached: the chip's own compiler (Mosaic) checks block shapes, layouts
and VMEM use that interpret mode never sees.  Shapes are the ones the
three paper networks' paper-faithful plans call at 224x224, batch 8.

The topology is described inside a fixture (never at import time): only
one process may load the TPU library, and every test worker imports this
file.  The persistent compilation cache is off around these compiles — a
compile for a described chip is written to it but cannot be read back.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_block.kernel import fused_chain_pallas
from repro.kernels.int8_gemm.kernel import int8_gemm_pallas


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return text


# (M, K, N) after int8_gemm's padding of M (and of N past one 256 tile)
@pytest.mark.parametrize("m,k,n", [
    pytest.param(6400, 288, 128, id="squeezenet-fire-28x28-3x3"),
    pytest.param(1792, 432, 192, id="squeezenet-fire-14x14-3x3"),
    pytest.param(1792, 48, 512, id="mobilenetv2-pw-14x14"),
    pytest.param(512, 480, 160, id="mobilenetv2-pw-7x7"),
    pytest.param(1792, 48, 48, id="shufflenetv2-pw-14x14"),
])
def test_int8_gemm_compiles_for_v5e(one_chip, m, k, n):
    fn = functools.partial(int8_gemm_pallas, tm=256, tn=256)
    text = _compile(fn, one_chip, ((m, k), jnp.int8), ((k, n), jnp.int8),
                    ((m, 1), jnp.float32), ((n,), jnp.float32))
    assert "%int8_gemm" in text


# (H, C_in, C_mid, C_out, stride, lead): the three stride-2 ShuffleNetV2
# down-sampling chains, and lead pw -> dw -> pw chains at 112x112
@pytest.mark.parametrize("h,c,cm,co,stride,lead", [
    pytest.param(56, 24, 24, 24, 2, False, id="shufflenetv2-stage2-down"),
    pytest.param(28, 48, 48, 48, 2, False, id="shufflenetv2-stage3-down"),
    pytest.param(14, 96, 96, 96, 2, False, id="shufflenetv2-stage4-down"),
    pytest.param(112, 8, 48, 8, 1, True, id="lead-112-stride1"),
    pytest.param(112, 8, 48, 16, 2, True, id="lead-112-stride2"),
])
def test_fused_chain_compiles_for_v5e(one_chip, h, c, cm, co, stride, lead):
    def fn(x, *weights):
        lw, lb = weights[:2] if lead else (None, None)
        return fused_chain_pallas(x, lw, lb, *weights[-4:], stride=stride,
                                  act_lead="relu6", act_dw="relu6")
    f32 = jnp.float32
    lead_shapes = (((c, cm), f32), ((cm,), f32)) if lead else ()
    text = _compile(fn, one_chip, ((8, h, h, c), f32), *lead_shapes,
                    ((3, 3, cm), f32), ((cm,), f32), ((cm, co), f32),
                    ((co,), f32))
    assert "%fused_chain" in text
