"""The plain reference agrees with the program's compiled engine on the
CPU, where both compute in exact float32 (the configuration's numerics
with float32 operands, as the program has them there), at a small size."""
import json

import jax
import numpy as np
import pytest

from conftest import ROOT, cpu_numerics
from chipbench.reference import Reference, rel_errors, weight_seed


@pytest.mark.parametrize("cfg_name,net", [
    ("mobilenetv2-0.5", "mobilenetv2"),
    ("shufflenetv2-0.5", "shufflenetv2"),
])
def test_reference_matches_the_engine_on_cpu(cfg_name, net):
    from repro.core.executor import compile_network
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network

    cfg = cpu_numerics(json.loads(
        (ROOT / "chipbench" / "configs" / f"{cfg_name}.json").read_text()))
    seed = 2**31 + 77                     # larger than a PRNG key takes
    x = (0.5 * np.random.default_rng(0).standard_normal((4, 64, 64, 3))
         ).astype(np.float32)
    mods = NETWORKS[net]()
    engine = compile_network(mods, partition_network(mods,
                                                     paper_faithful=True),
                             use_pallas=False)
    prepared = engine.prepare(init_network(
        mods, jax.random.PRNGKey(weight_seed(seed))))
    served = np.asarray(engine(prepared, x)).reshape(4, -1)
    assert rel_errors(served, Reference(cfg, seed, block=4)(x)).max() < 1e-5
