"""Pallas TPU kernel: fused FPGA-chain execution — the DHM analogue.

DHM's insight re-expressed for the TPU memory hierarchy: every intermediate
of a fused chain is produced and consumed inside VMEM — it never touches
HBM — exactly like DHM keeps inter-layer feature maps inside the FPGA
fabric.  Grid is (batch,); each program streams one feature map through the
whole chain.

Chain shapes (all static, burned into the kernel at trace time):

  * optional leading pw1x1 (+ its activation) — the ShuffleNetV2
    pw-dw-pw working branch, or MobileNetV2's expand+dw+project tail;
  * dw3x3 at stride 1 or 2 (+ activation none/relu/relu6) — stride-2
    covers the down-sampling stages that previously lowered node-by-node;
  * trailing pw1x1 on the MXU ((Ho*Wo, C) x (C, Co) matmul); its
    activation is applied by the caller.

The kernel takes the UNPADDED input block and SAME-pads the depthwise
input in a VMEM scratch ref (padding must happen after the leading
pointwise stage: ``act(0 @ w + b)`` is not zero at pad positions).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _act(x, kind: str):
    if kind == "relu":
        return jnp.maximum(x, 0.0)
    if kind == "relu6":
        return jnp.clip(x, 0.0, 6.0)
    return x


def _chain_kernel(*refs, has_lead: bool, stride: int, act_lead: str,
                  act_dw: str):
    if has_lead:
        (x_ref, lw_ref, lb_ref, dww_ref, dwb_ref, pww_ref, pwb_ref, out_ref,
         hp_ref) = refs
    else:
        x_ref, dww_ref, dwb_ref, pww_ref, pwb_ref, out_ref, hp_ref = refs
    x = x_ref[0]                            # (H, W, C) unpadded, in VMEM
    H, W = x.shape[0], x.shape[1]
    Ho, Wo = out_ref.shape[1], out_ref.shape[2]
    if has_lead:
        h = jnp.dot(x.reshape(H * W, -1), lw_ref[...],
                    preferred_element_type=jnp.float32)
        h = _act(h + lb_ref[...][None, :], act_lead)
        h = h.reshape(H, W, -1)
    else:
        h = x.astype(jnp.float32)
    # SAME pad for the 3x3/stride window (XLA's lo=total//2 split): the
    # padded map lives in a VMEM scratch ref, and each tap is a strided
    # load from it (Mosaic lowers a strided slice of a VALUE as a gather,
    # which it refuses)
    Hp, Wp, Cm = hp_ref.shape
    top = (Hp - H) // 2
    left = (Wp - W) // 2
    # zero only the pad border; the interior is overwritten below
    for r0, r1 in ((0, top), (top + H, Hp)):
        if r1 > r0:
            hp_ref[r0:r1, :, :] = jnp.zeros((r1 - r0, Wp, Cm), jnp.float32)
    for c0, c1 in ((0, left), (left + W, Wp)):
        if c1 > c0:
            hp_ref[:, c0:c1, :] = jnp.zeros((Hp, c1 - c0, Cm), jnp.float32)
    hp_ref[top:top + H, left:left + W, :] = h
    dww = dww_ref[...]
    acc = jnp.zeros((Ho, Wo, Cm), jnp.float32)
    for dy in range(3):
        for dx in range(3):
            sl = hp_ref[pl.ds(dy, Ho, stride=stride),
                        pl.ds(dx, Wo, stride=stride), :]
            acc += sl * dww[dy, dx][None, None, :]
    h2 = _act(acc + dwb_ref[...][None, None, :], act_dw)
    # pointwise: (Ho*Wo, C) @ (C, Co) on the MXU, fp32 accumulation
    hw = h2.reshape(Ho * Wo, -1).astype(x.dtype)
    out = jnp.dot(hw, pww_ref[...], preferred_element_type=jnp.float32)
    out = out + pwb_ref[...][None, :]
    out_ref[0] = out.reshape(Ho, Wo, -1).astype(out_ref.dtype)


def fused_chain_pallas(x, lead_w, lead_b, dw_w, dw_b, pw_w, pw_b, *,
                       stride: int = 1, act_lead: str = "none",
                       act_dw: str = "relu6", interpret=False):
    """x (B,H,W,C) -> (B,Ho,Wo,Co); intermediates stay in VMEM.

    ``lead_w``/``lead_b`` may be None (plain dw+pw pair)."""
    B, H, W, C = x.shape
    Ho, Wo = -(-H // stride), -(-W // stride)
    Cm = dw_w.shape[-1]
    Co = pw_w.shape[-1]
    has_lead = lead_w is not None
    kernel = functools.partial(_chain_kernel, has_lead=has_lead,
                               stride=stride, act_lead=act_lead,
                               act_dw=act_dw)
    in_specs = [pl.BlockSpec((1, H, W, C), lambda b: (b, 0, 0, 0))]
    args = [x]
    if has_lead:
        in_specs += [pl.BlockSpec((C, Cm), lambda b: (0, 0)),
                     pl.BlockSpec((Cm,), lambda b: (0,))]
        args += [lead_w, lead_b]
    in_specs += [
        pl.BlockSpec((3, 3, Cm), lambda b: (0, 0, 0)),
        pl.BlockSpec((Cm,), lambda b: (0,)),
        pl.BlockSpec((Cm, Co), lambda b: (0, 0)),
        pl.BlockSpec((Co,), lambda b: (0,)),
    ]
    args += [dw_w, dw_b, pw_w, pw_b]
    # SAME-padded depthwise input: Hp = (Ho-1)*stride + 3 when that exceeds H
    hp = max((Ho - 1) * stride + 3, H)
    wp = max((Wo - 1) * stride + 3, W)
    return pl.pallas_call(
        kernel,
        grid=(B,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Ho, Wo, Co), lambda b: (b, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Ho, Wo, Co), x.dtype),
        scratch_shapes=[pltpu.VMEM((hp, wp, Cm), jnp.float32)],
        interpret=interpret,
        name="fused_chain",
    )(*args)


def fused_dw_pw_pallas(x, dw_w, dw_b, pw_w, pw_b, *, interpret=False):
    """Back-compat wrapper: the original dw3x3(relu6)+pw1x1 pair."""
    return fused_chain_pallas(x, None, None, dw_w, dw_b, pw_w, pw_b,
                              stride=1, act_dw="relu6", interpret=interpret)
