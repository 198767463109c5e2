"""Distribution correctness that needs multiple (host) devices — run in
subprocesses so the main test session keeps a single device.

Covers: MoE expert-parallel dispatch vs the dense oracle, elastic restore
across topologies, and sharded-vs-single-device train-step equivalence.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

SRC = str(Path(__file__).resolve().parents[1] / "src")


# every mesh axis Auto: jax.make_mesh defaults to Explicit axes, which
# with_sharding_constraint under the LM sharding rules refuses
_PRELUDE = "import jax\nAUTO = (jax.sharding.AxisType.Auto,)\n"


def run_py(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c",
                          _PRELUDE + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_moe_ep_matches_dense_oracle():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs.base import MoEConfig
        from repro.configs import ShardingPolicy
        from repro.models.lm.moe import moe_schema, moe_dense, moe_ep
        from repro.models.lm.common import init_from_schema
        from repro.models.lm.sharding import AxisRules, use_rules

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             devices=jax.devices()[:8], axis_types=AUTO * 2)
        m = MoEConfig(n_routed=8, top_k=2, d_ff_expert=32, n_shared=1,
                      d_ff_shared=32, capacity_factor=4.0,
                      ep_axes=("model",), dispatch="ep")
        d = 16
        p = init_from_schema(moe_schema(d, m, 4), jax.random.PRNGKey(0),
                             jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, d)) * 0.5
        y_ref, aux_ref = moe_dense(p, x, m)
        pol = ShardingPolicy()
        rules = AxisRules(mesh, pol, m)
        with mesh, use_rules(rules):
            y_ep, aux_ep = jax.jit(lambda p_, x_: moe_ep(p_, x_, m))(p, x)
        err = float(jnp.abs(y_ref - y_ep).max())
        print("err", err, "aux", float(aux_ref), float(aux_ep))
        assert err < 1e-4, err
        assert abs(float(aux_ref) - float(aux_ep)) < 1e-4
    """)


def test_sharded_train_step_matches_single_device():
    run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.models.lm import model as lm
        from repro.models.lm.sharding import AxisRules, use_rules
        from repro.optim import make_optimizer
        from repro.train.steps import TrainState, make_train_step
        from repro.launch.specs import shardings_of
        import dataclasses

        cfg = reduced(get_config("llama3-8b"), dtype="float32")
        cfg = dataclasses.replace(cfg, policy=dataclasses.replace(
            cfg.policy, seq_parallel=True, fsdp=True))
        opt = make_optimizer("adamw")
        step = make_train_step(cfg, opt)
        params = lm.init_params(cfg, jax.random.PRNGKey(0))
        state = TrainState(jnp.zeros((), jnp.int32), params,
                           opt.init(params))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(1), (8, 64), 0, cfg.vocab)}
        # single device reference
        s1, m1 = jax.jit(step)(state, batch)
        # sharded over a (2, 4) mesh
        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             devices=jax.devices()[:8], axis_types=AUTO * 2)
        rules = AxisRules(mesh, cfg.policy, cfg.moe)
        with mesh, use_rules(rules):
            s2, m2 = jax.jit(step)(state, batch)
        print("loss", float(m1["loss"]), float(m2["loss"]))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4
        d = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()),
                         s1.params, s2.params)
        assert max(jax.tree.leaves(d)) < 1e-4
    """)


def test_elastic_restore_across_topologies(tmp_path):
    run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import CheckpointManager

        state = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                  "step": jnp.asarray(3)}}
        mesh4 = jax.make_mesh((4,), ("data",), devices=jax.devices()[:4],
                              axis_types=AUTO)
        sh4 = {{"w": NamedSharding(mesh4, P("data", None)),
                "step": NamedSharding(mesh4, P())}}
        state4 = jax.tree.map(jax.device_put, state, sh4)
        ck = CheckpointManager(r"{tmp_path}")
        ck.save(3, state4, blocking=True)

        mesh8 = jax.make_mesh((8,), ("data",), devices=jax.devices()[:8],
                              axis_types=AUTO)
        sh8 = {{"w": NamedSharding(mesh8, P(None, "data")),
                "step": NamedSharding(mesh8, P())}}
        restored, step = ck.restore(None, state, sh8)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.asarray(state["w"]))
        assert restored["w"].sharding.spec == P(None, "data")
        print("elastic restore ok", step)
    """)


def test_multipod_mesh_constructs():
    run_py("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        m2 = make_production_mesh(multi_pod=True)
        assert m1.devices.size == 256 and m1.axis_names == ("data", "model")
        assert m2.devices.size == 512 and m2.axis_names == ("pod", "data",
                                                            "model")
        print("meshes ok")
    """, devices=512)


def test_moe_ep2_hierarchical_matches_dense_oracle():
    run_py("""
        import jax, jax.numpy as jnp, dataclasses
        from repro.configs.base import MoEConfig
        from repro.configs import ShardingPolicy
        from repro.models.lm.moe import moe_schema, moe_dense, moe_ep
        from repro.models.lm.common import init_from_schema
        from repro.models.lm.sharding import AxisRules, use_rules

        mesh = jax.make_mesh((2, 4), ("data", "model"),
                             devices=jax.devices()[:8], axis_types=AUTO * 2)
        m = MoEConfig(n_routed=8, top_k=2, d_ff_expert=32, n_shared=0,
                      capacity_factor=4.0, ep_axes=("data", "model"),
                      dispatch="ep2")
        d = 16
        p = init_from_schema(moe_schema(d, m, 8), jax.random.PRNGKey(0),
                             jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (64, d)) * 0.5
        y_ref, aux_ref = moe_dense(p, x, m)
        rules = AxisRules(mesh, ShardingPolicy(), m)
        with mesh, use_rules(rules):
            y_ep, aux_ep = jax.jit(lambda p_, x_: moe_ep(p_, x_, m))(p, x)
        err = float(jnp.abs(y_ref - y_ep).max())
        print("ep2 err", err)
        assert err < 1e-4, err
        assert abs(float(aux_ref) - float(aux_ep)) < 1e-4
    """)
