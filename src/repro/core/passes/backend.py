"""Backend-lowering pass: emit the executable program from an annotated IR.

Consumes the ``ModuleIR`` produced by annotate/fuse/calibrate and returns a
``LoweredModule`` of three closures over static metadata:

  * ``prepare(params_m)``   one-time parameter lowering — FPGA weights leave
    fp32 exactly once (resident int8 + per-channel scale for the GEMM path,
    fake-quantized grids for the fused/conv paths);
  * ``run(prepared_m, x)``  the jit-traceable forward — node steps unrolled
    in graph order, every routing decision burned in at lowering time;
  * ``capture(prepared_m, x)`` the calibration forward — same steps, but
    records each calibration site's absolute-max activation so the network
    level can freeze scales into the prepared tree.

Batch invariance (the serving contract): every run-time step is
row-independent in the batch dimension.  Activation quantization is either
per-sample (``axis=0``) or a frozen per-tensor constant; the int8 GEMM
accumulates order-exactly; the remaining fp32 GEMMs run in fixed row
tiles (``rowsafe_matmul``) because XLA:CPU picks gemm blocking from the
full operand shapes and different blockings round differently; and the
global average pool sums in a fixed order (``gap_rowsafe``) because
XLA:TPU picks its reduction order by batch size.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.costmodel import ConvSpec
from repro.core.graph import Node
from repro.core.hetero import apply_act
from repro.core.passes.ir import (PATH_FQ, PATH_FREE, PATH_GCONV, PATH_GLUE,
                                  PATH_GPU, PATH_INT8, Chain, LoweredModule,
                                  ModuleIR)
from repro.kernels.fused_block.ops import fused_chain
from repro.kernels.int8_gemm.ops import int8_gemm
from repro.quant import (fake_quant, fake_quant_with_scale, quantize,
                         quantize_with_scale)


# --------------------------------------------------------------------------
# batch-invariant numeric building blocks
# --------------------------------------------------------------------------

_ROW_TILE = 8


def rowsafe_matmul(a, w, tile: int = _ROW_TILE):
    """a (M,K) @ w (K,N) computed in fixed (tile,K)@(K,N) row blocks.

    XLA:CPU picks gemm strategy (threading, cache blocking, small-M
    kernels) from the FULL operand shapes, and different K-panel groupings
    round differently — so row i of an (M,K) gemm is NOT bit-stable across
    M.  Padding M to a tile multiple and mapping the same fixed-shape gemm
    over row blocks pins the strategy, making every row's accumulation
    chain a function of that row alone.  This is what lets ``repro.serving``
    promise batch-size-independent logits.  Zero pad rows never enter a
    real row's chain; ``tile`` trades scan overhead (small tile, small M)
    against lost inter-block threading (large tile, large M)."""
    M, K = a.shape
    mp = -(-M // tile) * tile
    ap = jnp.pad(a, ((0, mp - M), (0, 0)))
    if mp == tile:
        return (ap @ w)[:M]
    _, out = jax.lax.scan(lambda c, t: (c, t @ w), None,
                          ap.reshape(-1, tile, K), unroll=4)
    return out.reshape(mp, -1)[:M]


def same_taps(x, k: int, s: int, fill=0.0):
    """SAME-pad x (NHWC) for a k*k/stride-s window (XLA's lo=total//2 split)
    and yield the k*k shifted strided (B,Ho,Wo,C) slices — the building
    block for the shift-and-add conv/pool lowerings below."""
    H, W = x.shape[1], x.shape[2]
    ho, wo = -(-H // s), -(-W // s)
    ph = max((ho - 1) * s + k - H, 0)
    pw = max((wo - 1) * s + k - W, 0)
    xp = jnp.pad(x, ((0, 0), (ph // 2, ph - ph // 2),
                     (pw // 2, pw - pw // 2), (0, 0)),
                 constant_values=fill)
    return [(dy, dx, xp[:, dy:dy + (ho - 1) * s + 1:s,
                        dx:dx + (wo - 1) * s + 1:s, :])
            for dy in range(k) for dx in range(k)]


def dw_shift_add(w, x, k: int, s: int):
    """Depthwise conv (multiplier 1) as k*k unrolled shift-and-adds — the
    dataflow of the Pallas fused kernel, and far faster than XLA's generic
    grouped-conv lowering on CPU.  w: (k,k,C)."""
    acc = None
    for dy, dx, sl in same_taps(x, k, s):
        term = sl * w[dy, dx]
        acc = term if acc is None else acc + term
    return acc


def spatial_tile(hw: int) -> int:
    """Row tile for a fp32 (B*Ho*Wo, K) GEMM: one sample's rows per tile,
    so batch 1 pays no padding and every batch size sees the same block
    shape.  Depends on the spatial size only — never on batch."""
    return -(-hw // _ROW_TILE) * _ROW_TILE


def conv_im2col(x, w, k: int, s: int):
    """SAME conv as a row-tiled (B*Ho*Wo, k*k*C) @ (k*k*C, Co) GEMM."""
    C, co = x.shape[-1], w.shape[-1]
    if k == 1 and s == 1:
        cols = x
    else:
        cols = jnp.concatenate([sl for _dy, _dx, sl in same_taps(x, k, s)],
                               axis=-1)
    y = rowsafe_matmul(cols.reshape(-1, k * k * C), w.reshape(-1, co),
                       tile=spatial_tile(cols.shape[1] * cols.shape[2]))
    return y.reshape(*cols.shape[:3], co)


def _xla_conv(spec: ConvSpec, act: str):
    if spec.kind == "dwconv" and spec.c_out == spec.c_in and spec.k <= 5:
        def run(p, x):
            y = dw_shift_add(p["w"].reshape(spec.k, spec.k, -1), x,
                             spec.k, spec.stride)
            return apply_act(y + p["b"], act)
        return run
    groups = spec.c_in if spec.kind == "dwconv" else spec.groups
    if groups == 1:
        # im2col + fixed-tile GEMM rather than conv_general_dilated: the
        # row-tiled GEMM is batch-invariant (see rowsafe_matmul) where
        # XLA:CPU's conv — itself a gemm over B*Ho*Wo rows — is not, and
        # for the small late-stage maps it also dodges conv's fixed per-op
        # cost.  The tile is a function of the spatial size only, so every
        # batch size lowers to the same per-block gemm shape.
        def run(p, x):
            y = conv_im2col(x, p["w"], spec.k, spec.stride)
            return apply_act(y + p["b"], act)
        return run

    def run(p, x):
        # grouped-conv fallback; unused by the paper networks (their only
        # grouped convs are depthwise, handled by the shift-add path) and
        # NOT batch-invariant — keep new graphs off this path if they are
        # to be served batched
        y = jax.lax.conv_general_dilated(
            x, p["w"], window_strides=(spec.stride, spec.stride),
            padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=groups)
        return apply_act(y + p["b"], act)
    return run


# --------------------------------------------------------------------------
# activation-quantization entry (per-sample fallback / frozen calibration)
# --------------------------------------------------------------------------

def _fq_in(p, x):
    """Fake-quant an activation: frozen per-tensor scale when the prepared
    tree carries one (calibrated plans), per-sample ``axis=0`` otherwise.
    The dict-key branch resolves at trace time — prepared structure is
    fixed per compiled signature."""
    if "x_scale" in p:
        return fake_quant_with_scale(x, p["x_scale"])
    return fake_quant(x, axis=0)


def _q_act(p, x):
    """int8-quantize an activation for the GEMM path.  Returns (q, scales)
    with scales shaped like ``quantize(x, axis=0)``'s keepdims output —
    per-sample scales, or the frozen per-tensor scale broadcast to that
    same shape so both modes feed the GEMM identically."""
    if "x_scale" in p:
        q = quantize_with_scale(x, p["x_scale"])
        s = jnp.broadcast_to(
            jnp.asarray(p["x_scale"], jnp.float32).reshape((1,) * x.ndim),
            (x.shape[0],) + (1,) * (x.ndim - 1))
        return q, s
    return quantize(x, axis=0)


# --------------------------------------------------------------------------
# per-path step builders: each returns (prepare(params) -> prepared,
#                                       run(prepared, x) -> y)
# --------------------------------------------------------------------------

def _lower_gpu(n: Node):
    if n.spec.kind == "fc":
        def run(p, x):
            y = rowsafe_matmul(x.reshape(x.shape[0], -1), p["w"])
            return apply_act(y + p["b"], n.act)
    else:
        run = _xla_conv(n.spec, n.act)
    return (lambda p: {"w": p["w"], "b": p["b"]}), run


def _lower_fpga_fq(n: Node):
    """FPGA conv that cannot use the int8 GEMM: weights fake-quantized once
    at prepare time, activation fake-quantized per call (or with the frozen
    calibration scale), XLA conv."""
    conv = _xla_conv(n.spec, n.act)

    def prepare(p):
        return {"w": fake_quant(p["w"], axis=-1), "b": p["b"]}

    def run(p, x):
        return conv(p, _fq_in(p, x))
    return prepare, run


def _lower_fpga_int8(n: Node, use_pallas: bool):
    """True-int8 path: any groups==1 FPGA conv (via im2col) or fc as an
    int8 GEMM with resident int8 weights.  The int32 accumulation is
    order-exact, so this path is batch-invariant with full cross-sample
    vectorization — no row tiling needed — and it is the faithful DHM
    substrate: the FPGA computes in 8-bit fixed point end to end."""
    spec = n.spec

    def prepare(p):
        w2d = p["w"].reshape(-1, spec.c_out)   # (k*k*C, co) for convs
        w_q, w_s = quantize(w2d, axis=-1)
        return {"w_q": w_q, "w_s": w_s.reshape(-1), "b": p["b"]}

    def run(p, x):
        # per-sample activation scales (axis=0) unless calibrated: each
        # request in a served batch quantizes exactly as it would alone
        x_q4, x_s4 = _q_act(p, x)
        if spec.kind == "fc":
            y = int8_gemm(x_q4.reshape(x.shape[0], -1), p["w_q"],
                          x_s4.reshape(x.shape[0], 1), p["w_s"],
                          use_pallas=use_pallas)
            return apply_act(y + p["b"], n.act)
        if spec.k == 1 and spec.stride == 1:
            cols = x_q4
        else:
            cols = jnp.concatenate(
                [sl for _dy, _dx, sl in
                 same_taps(x_q4, spec.k, spec.stride, fill=0)], axis=-1)
        lead = cols.shape[:3]
        x_s = jnp.broadcast_to(x_s4, (*lead, 1)).reshape(-1, 1)
        y = int8_gemm(cols.reshape(-1, cols.shape[-1]), p["w_q"], x_s,
                      p["w_s"], use_pallas=use_pallas)
        y = (y + p["b"]).reshape(*lead, spec.c_out)
        return apply_act(y, n.act)
    return prepare, run


def _lower_chain(chain: Chain, use_pallas: bool):
    """Fused FPGA chain through the ``fused_chain`` kernel: [lead pw] ->
    dw3x3/stride -> pw1x1, every intermediate VMEM-resident (no fake-quant
    round trips between the stages — the DHM on-chip residency
    semantics).  The XLA fallback replays the same dataflow with the
    batch-invariant shift-add + row-tiled GEMM primitives."""
    lead, dw, pw = chain.lead, chain.dw, chain.pw
    stride = chain.stride
    co = pw.spec.c_out

    def prepare(p_nodes):
        out = {"dw_w": fake_quant(p_nodes[dw.name]["w"].reshape(3, 3, -1),
                                  axis=-1),
               "dw_b": p_nodes[dw.name]["b"],
               "pw_w": fake_quant(p_nodes[pw.name]["w"].reshape(-1, co),
                                  axis=-1),
               "pw_b": p_nodes[pw.name]["b"]}
        if lead is not None:
            out["lead_w"] = fake_quant(
                p_nodes[lead.name]["w"].reshape(-1, lead.spec.c_out),
                axis=-1)
            out["lead_b"] = p_nodes[lead.name]["b"]
        return out

    if use_pallas:
        def run(p, x):
            y = fused_chain(_fq_in(p, x), p.get("lead_w"), p.get("lead_b"),
                            p["dw_w"], p["dw_b"], p["pw_w"], p["pw_b"],
                            stride=stride,
                            act_lead=lead.act if lead is not None else "none",
                            act_dw=dw.act, use_pallas=True)
            return apply_act(y, pw.act)
    else:
        def run(p, x):
            h = _fq_in(p, x)
            if lead is not None:
                hw = rowsafe_matmul(h.reshape(-1, h.shape[-1]), p["lead_w"],
                                    tile=spatial_tile(h.shape[1]
                                                      * h.shape[2]))
                h = apply_act(hw + p["lead_b"],
                              lead.act).reshape(*h.shape[:3], -1)
            h = apply_act(dw_shift_add(p["dw_w"], h, 3, stride) + p["dw_b"],
                          dw.act)
            y = rowsafe_matmul(h.reshape(-1, h.shape[-1]), p["pw_w"],
                               tile=spatial_tile(h.shape[1] * h.shape[2]))
            y = y + p["pw_b"]
            return apply_act(y.reshape(*h.shape[:3], co), pw.act)
    return prepare, run


def _lower_gconv(n: Node, frac: float):
    """Paper Fig. 2b input-channel split, lowered to ONE concatenated conv:
    channels [:g] carry the FPGA's quantized slice, [g:] the GPU's fp32
    slice; linearity in input channels makes the single conv equal the
    summed partials."""
    spec = n.spec
    g = max(1, int(round(spec.c_in * frac)))
    conv = _xla_conv(spec, n.act)

    def prepare(p):
        w = p["w"]
        w_cat = jnp.concatenate(
            [fake_quant(w[..., :g, :], axis=-1), w[..., g:, :]], axis=-2)
        return {"w": w_cat, "b": p["b"]}

    def run(p, x):
        x_cat = jnp.concatenate([_fq_in(p, x[..., :g]), x[..., g:]],
                                axis=-1)
        return conv(p, x_cat)
    return prepare, run, g


def _pool_shift(x, k: int, s: int, fill, combine):
    """Pooling as k*k shifted strided slices combined elementwise — the
    same trick as ``dw_shift_add``; XLA:CPU's ``reduce_window`` is a
    fixed-cost scalar loop that dwarfs the actual work."""
    acc = None
    for _dy, _dx, sl in same_taps(x, k, s, fill=fill):
        acc = sl if acc is None else combine(acc, sl)
    return acc


def gap_rowsafe(x):
    """Global average pool as a fixed chain of elementwise adds, down H
    and then across W: each output sums its own H*W terms in one order at
    every batch size.  A ``mean`` leaves that order to the backend, and
    XLA:TPU picks a different one for a batch of 1 than for a batch of 8
    (a 14x14 pool then differs in the last bit)."""
    s = x[:, 0]
    for i in range(1, x.shape[1]):
        s = s + x[:, i]
    t = s[:, 0]
    for j in range(1, x.shape[2]):
        t = t + s[:, j]
    return (t / (x.shape[1] * x.shape[2]))[:, None, None, :]


def _lower_pointfree(n: Node):
    """Parameter-free ops (pool/gap/concat/add/split/shuffle)."""
    spec = n.spec
    kind = spec.kind
    if kind == "maxpool":
        return lambda xs: _pool_shift(xs[0], spec.k, spec.stride,
                                      -jnp.inf, jnp.maximum)
    if kind == "avgpool":
        def run(xs):
            s = _pool_shift(xs[0], spec.k, spec.stride, 0.0, jnp.add)
            return s / (spec.k * spec.k)
        return run
    if kind == "gap":
        return lambda xs: gap_rowsafe(xs[0])
    if kind == "concat":
        return lambda xs: jnp.concatenate(xs, axis=-1)
    if kind == "add":
        return lambda xs: xs[0] + xs[1]
    if kind == "split":
        return lambda xs: xs[0][..., :spec.c_out]
    if kind == "shuffle":
        def run(xs):
            x = xs[0]
            b, h, w, c = x.shape
            return (x.reshape(b, h, w, 2, c // 2)
                    .transpose(0, 1, 2, 4, 3).reshape(b, h, w, c))
        return run
    raise ValueError(kind)


# --------------------------------------------------------------------------
# module-level emission
# --------------------------------------------------------------------------

_SITE_STATS = {
    # amplitude statistic a calibration capture records per quant site;
    # scale_from_amax turns any of them into a frozen per-tensor scale
    "amax": lambda v: jnp.max(jnp.abs(v)),
    "pct99": lambda v: jnp.percentile(jnp.abs(v), 99.0),
    # per-batch statistic is plain amax; the exponential averaging across
    # served batches happens at the serving layer (frozen-scale blending)
    "ema": lambda v: jnp.max(jnp.abs(v)),
}


def backend_pass(ir: ModuleIR) -> LoweredModule:
    m = ir.module
    chains_by_head = {c.head: c for c in ir.chains}
    consumed = {nm for c in ir.chains for nm in c.names()[1:]}
    calib = set(ir.calib_sites)
    site_stat = _SITE_STATS[ir.calibrator]

    preps: dict[str, Callable] = {}
    chain_params: dict[str, tuple[str, ...]] = {}
    # steps: (value_name, kind, payload) unrolled in node order at trace
    # time; param/chain payloads carry (prep_name, inputs, run, amax_site)
    # where amax_site is None (uncalibrated) or a capture spec.
    steps: list[tuple] = []
    for n in m.nodes:
        ann = ir.ann[n.name]
        if ann.path == PATH_GLUE:
            steps.append((n.name, "shuffle_glue", None))
            continue
        if n.name in consumed:
            continue                   # produced by its chain's head step
        if n.name in chains_by_head:
            chain = chains_by_head[n.name]
            prep, run = _lower_chain(chain, ir.use_pallas)
            preps[n.name] = prep
            chain_params[n.name] = chain.names()
            site = ("full",) if n.name in calib else None
            steps.append((chain.out, "param",
                          (n.name, n.inputs, run, site)))
            continue
        if ann.path == PATH_FREE:
            steps.append((n.name, "free", (n.inputs, _lower_pointfree(n))))
            continue
        if ann.path == PATH_GCONV:
            prep, run, g = _lower_gconv(n, ann.gconv_frac)
            site = ("gconv", g) if n.name in calib else None
        elif ann.path == PATH_INT8:
            prep, run = _lower_fpga_int8(n, ir.use_pallas)
            site = ("full",) if n.name in calib else None
        elif ann.path == PATH_FQ:
            prep, run = _lower_fpga_fq(n)
            site = ("full",) if n.name in calib else None
        else:
            assert ann.path == PATH_GPU
            prep, run = _lower_gpu(n)
            site = None
        preps[n.name] = prep
        steps.append((n.name, "param", (n.name, n.inputs, run, site)))

    def prepare(params_m):
        out = {}
        for nm, prep in preps.items():
            if nm in chain_params:     # chain: several raw param leaves
                out[nm] = prep({cn: params_m[cn]
                                for cn in chain_params[nm]})
            else:
                out[nm] = prep(params_m[nm])
        return out

    def _execute(prepared_m, x, record=None):
        values = {"in": x}
        for out_name, kind, payload in steps:
            if kind == "shuffle_glue":
                if out_name == "split":
                    half = m.node("split").spec.c_out
                    values["split"] = x[..., half:]
                    values["_identity"] = x[..., :half]
                else:
                    values["cat"] = jnp.concatenate(
                        [values["_identity"],
                         values[m.node("cat").inputs[1]]], axis=-1)
                continue
            if kind == "free":
                inputs, fn = payload
                values[out_name] = fn([values[i] for i in inputs])
                continue
            pname, inputs, fn, site = payload
            v = values[inputs[0]]
            if record is not None and site is not None:
                probe = v if site[0] == "full" else v[..., :site[1]]
                record[pname] = site_stat(probe)
            values[out_name] = fn(prepared_m[pname], v)
        out = values[m.output]
        if m.residual:
            out = out + x
        return out

    def run(prepared_m, x):
        return _execute(prepared_m, x)

    def capture(prepared_m, x):
        record: dict = {}
        y = _execute(prepared_m, x, record=record)
        return y, record

    return LoweredModule(ir, prepare, run, capture, steps)
