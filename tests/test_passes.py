"""Pass-pipeline tests: chain-fusion grouping, generalized fused-chain
kernel parity (stride-2 depthwise, pw-dw-pw branches), fused-chain
coverage of the three paper networks, and the batch-invariant pool."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.executor import compile_network
from repro.core.graph import NETWORKS, bottleneck, shuffle_unit
from repro.core.hetero import init_network, run_network
from repro.core.partitioner import (candidates, fused_chain_coverage,
                                    partition_network)
from repro.core.passes import build_ir, chain_groups
from repro.core.passes.backend import gap_rowsafe
from repro.kernels.fused_block.ops import fused_chain


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.maximum(jnp.linalg.norm(b),
                                                      1e-12))


def _scheme_plan(m, scheme):
    ps = [p for p in candidates(m) if p.scheme == scheme]
    assert ps, f"no {scheme} candidate for {m.kind}"
    return ps[0]


# --- chain grouping --------------------------------------------------------

def test_bottleneck_stride2_fuses_as_pair():
    m = bottleneck("b", 16, 24, 32, 2, 6)          # stride-2 dw
    plan = _scheme_plan(m, "fused_layer")
    groups = [g for g in chain_groups(m, plan) if len(g) > 1]
    assert [[n.name for n in g] for g in groups] == [["dw", "pw_proj"]]
    ir = build_ir(m, plan, use_pallas=False)
    assert len(ir.chains) == 1 and ir.chains[0].stride == 2


def test_shuffle_unit_pw_dw_pw_fuses_as_triple():
    m = shuffle_unit("s", 16, 48, False)
    plan = _scheme_plan(m, "fused_layer")
    groups = [g for g in chain_groups(m, plan) if len(g) > 1]
    assert [[n.name for n in g] for g in groups] == \
        [["b2_pw1", "b2_dw", "b2_pw2"]]
    ir = build_ir(m, plan, use_pallas=False)
    chain = ir.chains[0]
    assert chain.lead is not None and chain.stride == 1


def test_shuffle_down_fpga_fused_forms_two_chains():
    m = shuffle_unit("sd", 16, 48, True)
    plan = _scheme_plan(m, "fpga_fused")
    groups = [[n.name for n in g] for g in chain_groups(m, plan)
              if len(g) > 1]
    assert groups == [["b1_dw", "b1_pw"],
                      ["b2_pw1", "b2_dw", "b2_pw2"]]


def test_full_bottleneck_expand_chain_fuses_as_triple():
    m = bottleneck("b", 16, 24, 24, 1, 6)
    plan = _scheme_plan(m, "fpga_fused")            # pw_exp, dw, pw_proj
    groups = [[n.name for n in g] for g in chain_groups(m, plan)
              if len(g) > 1]
    assert groups == [["pw_exp", "dw", "pw_proj"]]


def test_paper_networks_reach_pair_level_coverage():
    """Every FPGA fused chain in the three paper networks lowers through
    the fusion pass with >= pair-level coverage: no dw->pw adjacency is
    left unfused inside any plan's fused tuple."""
    for net, builder in NETWORKS.items():
        mods = builder()
        for plans in (partition_network(mods, paper_faithful=True),
                      partition_network(mods, objective="edp")):
            plan_by = {p.module: p for p in plans}
            for m in mods:
                p = plan_by[m.name]
                if not p.fused:
                    continue
                groups = chain_groups(m, p)
                fused_names = {n.name for g in groups for n in g
                               if len(g) > 1}
                for g in groups:
                    for a, b in zip(g, g[1:]):
                        assert a.name in fused_names, (net, m.name, a.name)
                        assert b.name in fused_names, (net, m.name, b.name)


# --- parity: new fusion shapes vs the interpreted oracle -------------------

def _force_fused_plans(mods, scheme="fused_layer"):
    plans = []
    for m in mods:
        cands = [p for p in candidates(m) if p.scheme == scheme]
        if not cands:
            cands = [p for p in candidates(m) if p.scheme == "gpu_only"]
        plans.append(cands[0])
    return plans


@pytest.mark.parametrize("net", ["mobilenetv2", "shufflenetv2"])
@pytest.mark.parametrize("batch", [1, 4])
@pytest.mark.parametrize("use_pallas", [False, True])
def test_fused_chain_network_parity(net, batch, use_pallas):
    """Stride-2 depthwise chains (MBv2 down-bottlenecks) and pw-dw-pw
    branches (ShuffleNetV2 units) bit-match the interpreted oracle within
    the quantized tolerance, batch 1 and batched."""
    mods = NETWORKS[net]()
    plans = _force_fused_plans(mods)
    n_chains = sum(
        len(build_ir(m, p, use_pallas).chains)
        for m, p in zip(mods, plans))
    assert n_chains > 0, "plans formed no fused chains — test is vacuous"
    params = init_network(mods, jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (batch, 32, 32, 3))
    eng = compile_network(mods, plans, use_pallas=use_pallas)
    out = eng(eng.prepare(params), x)
    ref = run_network(mods, params, x, plans)
    assert out.shape == ref.shape
    assert _rel(out, ref) < 8e-2
    cos = float(jnp.sum(out * ref)
                / (jnp.linalg.norm(out) * jnp.linalg.norm(ref)))
    assert cos > 0.995


def test_stride2_chain_pallas_matches_xla_lowering():
    m = bottleneck("b", 8, 16, 24, 2, 6)
    plans = [_scheme_plan(m, "fused_layer")]
    params = init_network([m], jax.random.PRNGKey(0))
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (2, 8, 8, 16))
    outs = {}
    for up in (True, False):
        eng = compile_network([m], plans, use_pallas=up)
        outs[up] = eng(eng.prepare(params), x)
    assert _rel(outs[True], outs[False]) < 1e-4


# --- fused_chain kernel odd shapes -----------------------------------------

@pytest.mark.parametrize("hw,stride,lead", [
    ((9, 7), 2, False), ((8, 8), 1, True), ((11, 9), 2, True),
    # even maps at stride 2 pad only below/right, as ShuffleNetV2's
    # down-sampling chains (56/28/14) do
    ((14, 14), 2, False), ((16, 12), 2, True), ((7, 7), 1, False)])
def test_fused_chain_kernel_odd_shapes(hw, stride, lead):
    H, W = hw
    C, Cm, Co = 8, 12, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 7)
    x = jax.random.normal(ks[0], (2, H, W, C))
    lw = 0.3 * jax.random.normal(ks[1], (C, Cm)) if lead else None
    lb = 0.1 * jax.random.normal(ks[2], (Cm,)) if lead else None
    cmid = Cm if lead else C
    dw = 0.3 * jax.random.normal(ks[3], (3, 3, cmid))
    db = 0.1 * jax.random.normal(ks[4], (cmid,))
    pw = 0.3 * jax.random.normal(ks[5], (cmid, Co))
    pb = 0.1 * jax.random.normal(ks[6], (Co,))
    out = fused_chain(x, lw, lb, dw, db, pw, pb, stride=stride,
                      act_lead="relu", act_dw="none", use_pallas=True)
    ref = fused_chain(x, lw, lb, dw, db, pw, pb, stride=stride,
                      act_lead="relu", act_dw="none", use_pallas=False)
    Ho, Wo = -(-H // stride), -(-W // stride)
    assert out.shape == (2, Ho, Wo, Co)
    assert float(jnp.abs(out - ref).max()) < 1e-4


# --- global average pool ---------------------------------------------------

@pytest.mark.parametrize("hw", [(7, 7), (13, 13), (14, 14)])
def test_gap_rowsafe_matches_mean_at_every_batch_size(hw):
    x = jax.random.normal(jax.random.PRNGKey(0), (8, *hw, 40))
    gap = jax.jit(gap_rowsafe)
    out = np.asarray(gap(x))
    ref = np.asarray(x.mean(axis=(1, 2), keepdims=True))
    assert out.shape == (8, 1, 1, 40)
    assert np.abs(out - ref).max() < 1e-6
    rows = np.concatenate([np.asarray(gap(x[i:i + 1])) for i in range(8)])
    assert rows.tobytes() == out.tobytes()


# --- coverage accounting ---------------------------------------------------

def test_fused_chain_coverage_counts_paper_networks():
    for net, builder in NETWORKS.items():
        mods = builder()
        plans = partition_network(mods, paper_faithful=True)
        cov = fused_chain_coverage(mods, plans)
        assert 0.0 <= cov["coverage"] <= 1.0
        assert cov["fused_nodes"] <= cov["fpga_nodes"]
        forced = _force_fused_plans(mods)
        cov_forced = fused_chain_coverage(mods, forced)
        if cov_forced["fpga_nodes"]:
            assert cov_forced["coverage"] > 0.9, (net, cov_forced)
