"""Operation and byte counts on hand-worked shapes, and the networks'
FLOPs against the program's own cost model."""
import json

import pytest

from conftest import ROOT
from chipbench.costs import fused_chain, int8_gemm
from chipbench.roofline import share
from chipbench.run import Run


def config(name):
    return json.loads((ROOT / "chipbench" / "configs" / f"{name}.json")
                      .read_text())


def test_int8_gemm_shapes_and_work():
    cfg = config("mobilenetv2-0.5")
    # bneck13 expands 48 -> 288 at 14x14, projects 288 -> 80 at 7x7 (its dw
    # has stride 2); bneck16 expands 80 -> 480 and projects 480 -> 160
    assert int8_gemm.shapes(cfg) == [(196, 48, 288), (49, 288, 80),
                                     (49, 80, 480), (49, 480, 160)]
    ops, nbytes = int8_gemm.work(cfg, slots=1, batches=1)[3]
    assert ops == 2 * 49 * 480 * 160 == 7_526_400
    # x int8, a scale per row, out float32, then w int8 and a scale per
    # column once per batch
    assert nbytes == 49 * 480 + 4 * 49 + 4 * 49 * 160 + 480 * 160 + 4 * 160
    ops32, bytes32 = int8_gemm.work(cfg, slots=32, batches=1)[3]
    assert ops32 == 32 * ops
    assert bytes32 == 32 * (nbytes - 480 * 160 - 4 * 160) + 480 * 160 + 640


def test_fused_chain_work():
    cfg = config("shufflenetv2-0.5")
    work = fused_chain.work(cfg, slots=1, batches=1)
    assert len(work) == 3
    # stage4_down's branch: dw 3x3 stride 2 on 14x14x96, then pw 96 -> 96
    ops, nbytes = work[2]
    assert ops == 2 * 7 * 7 * 9 * 96 + 2 * 7 * 7 * 96 * 96 == 987_840
    assert nbytes == 4 * (14 * 14 * 96 + 7 * 7 * 96) + 4 * (
        9 * 96 + 96 + 96 * 96 + 96)
    assert int8_gemm.shapes(cfg)[0] == (196, 48, 48)


@pytest.mark.parametrize("cfg_name,net", [
    ("mobilenetv2-0.5", "mobilenetv2"),
    ("shufflenetv2-0.5", "shufflenetv2"),
])
def test_network_flops_match_the_cost_model(cfg_name, net):
    """The cost ``mfu`` reads (``conv_net`` for an architecture with no
    file of its own) against the program's cost model."""
    from repro.core.graph import NETWORKS
    cfg = config(cfg_name)
    cost = Run.cost(cfg["architecture"], "conv_net")
    macs = sum(m.total_macs() for m in NETWORKS[net]())
    assert cost.flops_per_image(cfg) == 2 * macs


def test_roofline_share_and_bound():
    # 1e9 ops at 1e12 ops/s is 1 ms; 1e6 bytes at 1e9 B/s is 1 ms; the
    # larger bounds each call
    pct, bound = share([(2e9, 1e6), (1e9, 4e6)], 1e12, 1e9, 0.012)
    assert pct == pytest.approx(100 * (0.002 + 0.004) / 0.012)
    assert bound == "memory"
