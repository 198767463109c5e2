"""Mixture-of-Experts: shared + routed top-k with two dispatch strategies.

``dense``   — every expert computes every token, combined with routing
              weights.  O(T*E) FLOPs: only for smoke tests and as the oracle
              the EP path is validated against.
``ep``      — expert parallelism: capacity-based all_to_all dispatch inside
              ``shard_map`` over the config's ``ep_axes``.  This is the
              paper's GConv partition expressed at datacentre scale: expert
              groups execute in parallel on disjoint devices and results are
              concatenated/combined afterwards, latency = max(group) + comm.

Local expert compute is either ``scan`` (masked loop over local experts,
E_loc x FLOPs waste, differentiable everywhere — default for training) or
``ragged`` (sort + jax.lax.ragged_dot, no waste — serving/perf path).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.configs.base import MoEConfig
from repro.models.lm.common import Schema, ffn_apply, ffn_schema, prefix_schema
from repro.models.lm.sharding import current_rules


def _pad_experts(m: MoEConfig, n_ep: int) -> int:
    """Experts padded up to a multiple of the EP group count."""
    e = m.n_routed
    return ((e + n_ep - 1) // n_ep) * n_ep if n_ep > 1 else e


def moe_schema(d: int, m: MoEConfig, n_ep: int = 1) -> Schema:
    e_pad = _pad_experts(m, n_ep)
    s: Schema = {
        "router/w": ((d, m.n_routed), ("embed", None), "normal"),
        "experts/w_gate": ((e_pad, d, m.d_ff_expert), ("experts", "embed_ep", None), "normal"),
        "experts/w_up": ((e_pad, d, m.d_ff_expert), ("experts", "embed_ep", None), "normal"),
        "experts/w_down": ((e_pad, m.d_ff_expert, d), ("experts", None, "embed_ep"), "normal"),
    }
    if m.n_shared:
        s.update(prefix_schema("shared", ffn_schema(d, m.n_shared * m.d_ff_shared)))
        s["shared_gate/w"] = ((d, 1), ("embed", None), "normal")
    return s


def _route(x, wr, top_k: int):
    """Router: returns (weights (T,k), idx (T,k), (f, p) balance stats)."""
    logits = jnp.einsum("td,de->te", x, wr,
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)
    weights = vals / jnp.maximum(vals.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balance statistics
    e = wr.shape[-1]
    f = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
    f = f / jnp.maximum(f.sum(), 1.0)
    p = probs.mean(axis=0)
    return weights, idx, (f, p)


def _aux_from_stats(f, p):
    return f.shape[-1] * jnp.sum(f * p)


def _shared_out(p, x):
    """Shared-expert FFN on flattened (T, d) tokens — token-sharded layout."""
    from repro.models.lm.sharding import lc
    if "shared" not in p:
        return 0.0
    y = ffn_apply(p["shared"], x, hidden_axes=("tokens", None))
    y = lc(y, "tokens", None)
    g = jax.nn.sigmoid(
        jnp.einsum("td,dk->tk", x, p["shared_gate"]["w"],
                   preferred_element_type=jnp.float32))
    return y * g.astype(y.dtype)


# ---------------------------------------------------------------------------
# Dense dispatch (oracle / smoke)
# ---------------------------------------------------------------------------

def moe_dense(p, x, m: MoEConfig):
    """x (T, d) -> (y (T, d), aux)."""
    weights, idx, (f_, p_) = _route(x, p["router"]["w"], m.top_k)
    aux = _aux_from_stats(f_, p_)
    e = m.n_routed

    def per_expert(carry, ew):
        wg, wu, wd, ei = ew
        h = jax.nn.silu((x @ wg).astype(jnp.float32)).astype(x.dtype) * (x @ wu)
        y_e = h @ wd                                    # (T, d)
        gate = jnp.sum(jnp.where(idx == ei, weights, 0.0), axis=-1)  # (T,)
        return carry + y_e * gate[:, None].astype(y_e.dtype), None

    init = jnp.zeros_like(x)
    ew = (p["experts"]["w_gate"][:e], p["experts"]["w_up"][:e],
          p["experts"]["w_down"][:e], jnp.arange(e))
    y, _ = jax.lax.scan(per_expert, init, ew)
    return y + _shared_out(p, x), aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch (shard_map + all_to_all)
# ---------------------------------------------------------------------------

def _ep_local(x, wr, wg, wu, wd, m: MoEConfig, ep_axes, n_ep: int,
              local_compute: str, tok_axes):
    """Per-device body under shard_map.  x (T_loc, d); w* (E_loc, ...)."""
    t, d = x.shape
    e_loc = wg.shape[0]
    e_pad = e_loc * n_ep
    weights, idx, (f_, p_) = _route(x, wr, m.top_k)     # idx in [0, n_routed)
    # global load-balance loss: average the STATS across every token shard,
    # then take the product — identical to the dense oracle's global aux
    aux = _aux_from_stats(jax.lax.pmean(f_, tok_axes),
                          jax.lax.pmean(p_, tok_axes))

    flat_idx = idx.reshape(-1)                          # (T*k,)
    flat_w = weights.reshape(-1)
    dst = flat_idx // e_loc                             # destination EP shard
    lid = flat_idx % e_loc                              # local expert on dst
    cap = int(max(8, round(t * m.top_k * m.capacity_factor / n_ep)))
    # slot = rank of this assignment among those to the same dst
    onehot = (dst[:, None] == jnp.arange(n_ep)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, dst[:, None], axis=1)[:, 0]
    keep = slot < cap
    send_idx = jnp.where(keep, dst * cap + slot, n_ep * cap)   # OOB -> drop

    tok = jnp.arange(t * m.top_k) // m.top_k
    buf_x = jnp.zeros((n_ep * cap, d), x.dtype).at[send_idx].set(
        x[tok], mode="drop")
    buf_l = jnp.zeros((n_ep * cap,), jnp.int32).at[send_idx].set(
        lid + 1, mode="drop")                            # 0 = empty

    a2a = partial(jax.lax.all_to_all, axis_name=ep_axes, split_axis=0,
                  concat_axis=0, tiled=True)
    recv_x = a2a(buf_x)                                  # (n_ep*cap, d)
    recv_l = a2a(buf_l) - 1                              # -1 = empty

    if local_compute == "ragged" and e_loc > 1:
        grp = jnp.where(recv_l < 0, e_loc - 1, recv_l)
        order = jnp.argsort(grp, stable=True)
        xs = recv_x[order]
        gs = jnp.zeros((e_loc,), jnp.int32).at[grp].add(1)
        h = jax.nn.silu(jax.lax.ragged_dot(xs, wg, gs).astype(jnp.float32))
        h = h.astype(x.dtype) * jax.lax.ragged_dot(xs, wu, gs)
        ys = jax.lax.ragged_dot(h, wd, gs)
        y_rows = jnp.zeros_like(ys).at[order].set(ys)
    elif e_loc == 1:
        h = jax.nn.silu((recv_x @ wg[0]).astype(jnp.float32)).astype(x.dtype)
        y_rows = (h * (recv_x @ wu[0])) @ wd[0]
    else:
        def per_local(carry, ew):
            g_, u_, d_, ei = ew
            h = jax.nn.silu((recv_x @ g_).astype(jnp.float32)).astype(x.dtype)
            y_e = (h * (recv_x @ u_)) @ d_
            sel = (recv_l == ei)[:, None]
            return carry + jnp.where(sel, y_e, 0.0), None
        y_rows, _ = jax.lax.scan(
            per_local, jnp.zeros_like(recv_x),
            (wg, wu, wd, jnp.arange(e_loc)))

    back = a2a(y_rows)                                   # (n_ep*cap, d)
    safe = jnp.where(keep, dst * cap + slot, 0)
    y_tk = back[safe] * keep[:, None].astype(back.dtype)  # (T*k, d)
    y = jnp.zeros_like(x).at[tok].add(y_tk * flat_w[:, None].astype(back.dtype))
    return y, aux


def _scatter_to(dst, payloads, n_dst: int, cap: int):
    """Capacity-scatter rows to per-destination buffers.

    dst (R,) int32; payloads: list of (R, ...) arrays.  Returns
    ([(n_dst*cap, ...)], keep (R,), slot (R,)).
    """
    r = dst.shape[0]
    onehot = (dst[:, None] == jnp.arange(n_dst)[None, :]).astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - 1
    slot = jnp.take_along_axis(pos, dst[:, None], axis=1)[:, 0]
    keep = slot < cap
    send_idx = jnp.where(keep, dst * cap + slot, n_dst * cap)
    bufs = []
    for pay in payloads:
        shape = (n_dst * cap,) + pay.shape[1:]
        bufs.append(jnp.zeros(shape, pay.dtype).at[send_idx].set(
            pay, mode="drop"))
    return bufs, keep, slot


def _ep2_local(x, wr, wg, wu, wd, m: MoEConfig, ax_d, ax_m, n_d, n_m,
               tok_axes, local_compute: str):
    """Hierarchical 2-hop expert dispatch (beyond-paper §Perf):

    expert e lives on device (d, m_) = (e // (n_m*E_loc*?) ...) arranged
    row-major; tokens hop all_to_all over the `data` axis first, then over
    `model`.  Each collective spans 16 devices instead of 256, which (a)
    keeps the XLA while loop rolled (full-mesh a2a triggers loop unrolling)
    and (b) matches torus link locality.
    """
    t, d = x.shape
    e_loc = wg.shape[0]
    weights, idx, (f_, p_) = _route(x, wr, m.top_k)
    aux = _aux_from_stats(jax.lax.pmean(f_, tok_axes),
                          jax.lax.pmean(p_, tok_axes))

    flat_idx = idx.reshape(-1)
    flat_w = weights.reshape(-1)
    tok = jnp.arange(t * m.top_k) // m.top_k
    # expert e -> (d_dst, m_dst, lid)
    per_d = n_m * e_loc
    d_dst = flat_idx // per_d
    m_dst = (flat_idx % per_d) // e_loc
    lid = flat_idx % e_loc

    cap1 = int(max(8, round(t * m.top_k * m.capacity_factor / n_d)))
    (bx1, bm1, bl1), keep1, slot1 = _scatter_to(
        d_dst, [x[tok], m_dst + 1, lid.astype(jnp.int32)], n_d, cap1)
    a2a_d = partial(jax.lax.all_to_all, axis_name=ax_d, split_axis=0,
                    concat_axis=0, tiled=True)
    rx1, rm1, rl1 = a2a_d(bx1), a2a_d(bm1), a2a_d(bl1)

    # hop 2: within the data row, to the model column owning the expert
    valid1 = rm1 > 0
    cap2 = int(max(8, round(t * m.top_k * m.capacity_factor / (n_d * n_m)
                            * n_d)))
    dst2 = jnp.where(valid1, rm1 - 1, n_m)           # invalid -> dropped
    (bx2, bl2), keep2, slot2 = _scatter_to(
        dst2, [rx1, rl1 + 1], n_m, cap2)
    a2a_m = partial(jax.lax.all_to_all, axis_name=ax_m, split_axis=0,
                    concat_axis=0, tiled=True)
    rx2, rl2 = a2a_m(bx2), a2a_m(bl2)

    lid2 = rl2 - 1
    if e_loc == 1:
        h = jax.nn.silu((rx2 @ wg[0]).astype(jnp.float32)).astype(x.dtype)
        y2 = (h * (rx2 @ wu[0])) @ wd[0]
    else:
        def per_local(carry, ew):
            g_, u_, dn_, ei = ew
            h = jax.nn.silu((rx2 @ g_).astype(jnp.float32)).astype(x.dtype)
            y_e = (h * (rx2 @ u_)) @ dn_
            return carry + jnp.where((lid2 == ei)[:, None], y_e, 0.0), None
        y2, _ = jax.lax.scan(per_local, jnp.zeros_like(rx2),
                             (wg, wu, wd, jnp.arange(e_loc)))

    # reverse hop 2
    back2 = a2a_m(y2)
    safe2 = jnp.where(keep2, dst2 * cap2 + slot2, 0)
    y1 = back2[safe2] * (keep2 & valid1)[:, None].astype(back2.dtype)
    # reverse hop 1
    back1 = a2a_d(y1)
    safe1 = jnp.where(keep1, d_dst * cap1 + slot1, 0)
    y_tk = back1[safe1] * keep1[:, None].astype(back1.dtype)
    y = jnp.zeros_like(x).at[tok].add(
        y_tk * flat_w[:, None].astype(back1.dtype))
    return y, aux


def moe_ep(p, x, m: MoEConfig, local_compute: str = "scan"):
    """x (T, d) sharded over (batch x seq); EP over m.ep_axes."""
    rules = current_rules()
    if rules is None or rules.mesh is None:
        return moe_dense(p, x, m)
    mesh = rules.mesh
    ep_axes = tuple(a for a in m.ep_axes if a in mesh.axis_names)
    n_ep = 1
    for a in ep_axes:
        n_ep *= mesh.shape[a]
    if n_ep == 1:
        return moe_dense(p, x, m)

    from jax.sharding import PartitionSpec as P
    # tokens sharded over every batch-bearing axis + model (SP layout)
    tok_axes = tuple(a for a in ("pod", "data", "model") if a in mesh.axis_names)
    n_tok = 1
    for a in tok_axes:
        n_tok *= mesh.shape[a]
    t_global = x.shape[0]
    t_pad = -(-t_global // n_tok) * n_tok          # decode: pad tiny batches
    xp = jnp.pad(x, ((0, t_pad - t_global), (0, 0))) if t_pad != t_global else x
    from repro.models.lm.sharding import lc
    xp = lc(xp, "tokens", None)
    x_spec = P(tok_axes, None)
    # expert weights enter the shard_map gathered over the FSDP dim
    e_spec = P(ep_axes, None, None)
    out_specs = (x_spec, P())

    if m.dispatch == "ep2" and len(ep_axes) == 2:
        ax_d, ax_m = ep_axes
        n_d, n_m = mesh.shape[ax_d], mesh.shape[ax_m]

        def body(x_, wr_, wg_, wu_, wd_):
            return _ep2_local(x_, wr_, wg_, wu_, wd_, m, ax_d, ax_m,
                              n_d, n_m, tok_axes, local_compute)
    else:
        def body(x_, wr_, wg_, wu_, wd_):
            return _ep_local(x_, wr_, wg_, wu_, wd_, m, ep_axes, n_ep,
                             local_compute, tok_axes)

    y, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), e_spec, e_spec, e_spec),
        out_specs=out_specs, check_vma=False,
    )(xp, p["router"]["w"], p["experts"]["w_gate"], p["experts"]["w_up"],
      p["experts"]["w_down"])
    y = lc(y, "tokens", None)
    if t_pad != t_global:
        y = y[:t_global]
    return y + _shared_out(p, x), aux


def moe_apply(p, x, m: MoEConfig, deterministic_dispatch: str | None = None):
    """x (..., d) -> (y, aux_loss).  Flattens leading dims."""
    from repro.models.lm.sharding import lc
    shape = x.shape
    x2 = x.reshape(-1, shape[-1])
    if x2.shape[0] % 256 == 0:       # keep SP token layout through the moe
        x2 = lc(x2, "tokens", None)
    dispatch = deterministic_dispatch or m.dispatch
    if dispatch == "dense":
        y, aux = moe_dense(p, x2, m)
    else:
        y, aux = moe_ep(p, x2, m)
    return y.reshape(shape), aux
