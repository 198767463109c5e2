"""Batched multi-plan serving: correctness (batched results bit-match
per-request ``compile_network`` calls across networks and partitioner
schemes), scheduling (bucket selection, deadline flush, multi-plan
isolation), and executor-cache behaviour under a live server."""
import time

import jax
import jax.numpy as jnp
import pytest

from repro.core.executor import cache_stats, clear_cache, compile_network
from repro.core.graph import NETWORKS, bottleneck, fire, shuffle_unit
from repro.core.hetero import init_network
from repro.core.partitioner import candidates, partition_network
from repro.serving import (DynamicBatcher, HeteroServer, pad_batch,
                           percentile, pick_bucket)

RES = 24


def _assert_bitmatch(server, name, engine, prepared, images, timeout=60):
    futs = [server.submit(name, x) for x in images]
    outs = [f.result(timeout=timeout) for f in futs]
    for x, out in zip(images, outs):
        ref = engine(prepared, x[None])[0]
        assert out.shape == ref.shape
        assert bool(jnp.all(out == ref)), \
            f"{name}: served result differs from per-request engine call"


def _images(n, hw, c, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), n)
    return [0.5 * jax.random.normal(k, (*hw, c)) for k in ks]


# --- correctness: full networks, interleaved multi-plan --------------------

def test_full_networks_bitmatch_interleaved():
    """All three paper networks resident at once; interleaved requests come
    back bit-identical to batch-1 engine calls despite shared batches."""
    server = HeteroServer(buckets=(1, 4, 8), max_wait_ms=5.0)
    refs = {}
    for net, builder in NETWORKS.items():
        mods = builder()
        plans = partition_network(mods, paper_faithful=True)
        params = init_network(mods, jax.random.PRNGKey(0))
        server.register(net, mods, plans, params, input_hw=(RES, RES))
        eng = compile_network(mods, plans)
        refs[net] = (eng, eng.prepare(params))
    imgs = {net: _images(6, (RES, RES), 3, seed=i)
            for i, net in enumerate(NETWORKS)}
    with server:
        futs = [(net, x, server.submit(net, x))
                for i in range(6) for net, x in
                ((n, imgs[n][i]) for n in NETWORKS)]
        for net, x, f in futs:
            out = f.result(timeout=120)
            eng, prep = refs[net]
            assert bool(jnp.all(out == eng(prep, x[None])[0]))
    snap = server.metrics.snapshot()
    assert snap["completed"] == 18 and snap["failed"] == 0


# --- correctness: every partitioner scheme through the server --------------

def _scheme_case(m, scheme):
    ps = [p for p in candidates(m) if p.scheme == scheme]
    assert ps, f"no {scheme} candidate for {m.kind}"
    return [m], [ps[0]]


SCHEME_CASES = [
    ("fire", lambda: fire("f", 16, 64, 16, 64),
     ["gpu_only", "fpga_fused", "parallel_branch", "gconv_split"]),
    ("bottleneck", lambda: bottleneck("b", 16, 24, 24, 1, 6),
     ["gpu_only", "fpga_fused", "dwconv_split", "fused_layer"]),
    ("shuffle_unit", lambda: shuffle_unit("s", 16, 48, False),
     ["fpga_fused", "dwconv_split", "fused_layer"]),
    ("shuffle_unit_down", lambda: shuffle_unit("sd", 16, 48, True),
     ["parallel_branch"]),
]


@pytest.mark.parametrize("kind,builder,schemes", SCHEME_CASES,
                         ids=[c[0] for c in SCHEME_CASES])
def test_scheme_bitmatch(kind, builder, schemes):
    for scheme in schemes:
        mods, plans = _scheme_case(builder(), scheme)
        params = init_network(mods, jax.random.PRNGKey(1))
        server = HeteroServer(buckets=(1, 4), max_wait_ms=3.0)
        server.register(kind, mods, plans, params, input_hw=(16, 16))
        eng = compile_network(mods, plans)
        prep = eng.prepare(params)
        c_in = mods[0].nodes[0].spec.c_in
        with server:
            _assert_bitmatch(server, kind, eng, prep,
                             _images(5, (16, 16), c_in, seed=2))


# --- scheduling: buckets -----------------------------------------------------

def test_pick_bucket():
    assert pick_bucket(1, (1, 4, 8, 32)) == 1
    assert pick_bucket(2, (1, 4, 8, 32)) == 4
    assert pick_bucket(4, (1, 4, 8, 32)) == 4
    assert pick_bucket(9, (1, 4, 8, 32)) == 32
    assert pick_bucket(40, (1, 4, 8, 32)) == 32   # capped at the largest


def test_deadline_take_pads_small_splits_large():
    ladder = (1, 4, 8, 32)
    # small overshoot: pad up to the covering bucket in one flush
    assert DynamicBatcher._deadline_take(2, ladder) == 2    # -> bucket 4
    assert DynamicBatcher._deadline_take(5, ladder) == 5    # -> bucket 8
    assert DynamicBatcher._deadline_take(8, ladder) == 8    # exact
    # >half the covering bucket would be pad: flush the largest full
    # bucket, leave the remainder queued
    assert DynamicBatcher._deadline_take(10, ladder) == 8
    assert DynamicBatcher._deadline_take(9, ladder) == 8
    assert DynamicBatcher._deadline_take(17, ladder) == 17  # -> bucket 32
    assert DynamicBatcher._deadline_take(32, ladder) == 32


def test_pad_batch_pads_with_inert_zeros():
    xs = [jnp.ones((4, 4, 3)), 2 * jnp.ones((4, 4, 3))]
    xb = pad_batch(xs, 4)
    assert xb.shape == (4, 4, 4, 3)
    assert bool(jnp.all(xb[0] == 1)) and bool(jnp.all(xb[1] == 2))
    assert bool(jnp.all(xb[2:] == 0))


def test_full_bucket_flushes_by_size():
    m = fire("f", 8, 16, 4, 8)
    server = HeteroServer(buckets=(1, 4), max_wait_ms=5000.0)
    server.register("f", [m], None, input_hw=(8, 8))
    with server:
        futs = [server.submit("f", x) for x in _images(4, (8, 8), 16)]
        for f in futs:
            f.result(timeout=60)
    snap = server.metrics.snapshot()
    # a full bucket must not wait for the (5 s) deadline
    assert snap["size_flushes"] >= 1 and snap["deadline_flushes"] == 0
    assert snap["padded_slots"] == 0


def test_partial_group_flushes_by_deadline_into_padded_bucket():
    m = fire("f", 8, 16, 4, 8)
    server = HeteroServer(buckets=(1, 4), max_wait_ms=30.0)
    server.register("f", [m], None, input_hw=(8, 8))
    with server:
        t0 = time.monotonic()
        futs = [server.submit("f", x) for x in _images(2, (8, 8), 16)]
        for f in futs:
            f.result(timeout=60)
        waited = time.monotonic() - t0
    snap = server.metrics.snapshot()
    assert snap["deadline_flushes"] >= 1
    assert snap["padded_slots"] == 2          # 2 requests -> bucket 4
    assert waited >= 0.025                    # sat out the max-wait window


def test_shutdown_flushes_backlog_larger_than_max_bucket():
    """A queued backlog exceeding the largest bucket must drain in chunks
    at shutdown, not error out."""
    m = fire("f", 8, 16, 4, 8)
    server = HeteroServer(buckets=(1, 4), max_wait_ms=10000.0)
    server.register("f", [m], None, input_hw=(8, 8))
    eng = compile_network([m], None)
    prep = eng.prepare(server._entries["f"].params)
    server.start()
    server._stop.set()                      # idle the drain loop...
    time.sleep(0.2)
    imgs = _images(10, (8, 8), 16, seed=5)  # ...then queue 10 > bucket 4
    futs = [server.submit("f", x) for x in imgs]
    server.shutdown()
    for x, f in zip(imgs, futs):
        out = f.result(timeout=60)
        assert bool(jnp.all(out == eng(prep, x[None])[0]))


def test_submit_validates_network_and_shape():
    server = HeteroServer(buckets=(1,))
    with pytest.raises(KeyError, match="unregistered"):
        server.submit("nope", jnp.zeros((8, 8, 16)))
    server.register("f", [fire("f", 8, 16, 4, 8)], None, input_hw=(8, 8))
    with pytest.raises(ValueError, match="expected an image"):
        server.submit("f", jnp.zeros((8, 8, 4)))


# --- scheduling: multi-plan isolation --------------------------------------

def test_multi_plan_isolation_same_network_different_plans():
    """The same topology under two different plans serves from two distinct
    engines (keyed by plan signature) — requests never cross-route."""
    mods_a = NETWORKS["mobilenetv2"]()
    mods_b = NETWORKS["mobilenetv2"]()
    plans_a = partition_network(mods_a, paper_faithful=True)
    plans_b = partition_network(mods_b, objective="gpu_only")
    params = init_network(mods_a, jax.random.PRNGKey(0))
    server = HeteroServer(buckets=(1, 4), max_wait_ms=3.0)
    server.register("hetero", mods_a, plans_a, params, input_hw=(RES, RES))
    server.register("gpu", mods_b, plans_b, params, input_hw=(RES, RES))
    eng_a = compile_network(mods_a, plans_a)
    eng_b = compile_network(mods_b, plans_b)
    assert eng_a is not eng_b
    prep_a, prep_b = eng_a.prepare(params), eng_b.prepare(params)
    imgs = _images(4, (RES, RES), 3, seed=3)
    with server:
        fa = [server.submit("hetero", x) for x in imgs]
        fb = [server.submit("gpu", x) for x in imgs]
        outs_a = [f.result(timeout=120) for f in fa]
        outs_b = [f.result(timeout=120) for f in fb]
    for x, oa, ob in zip(imgs, outs_a, outs_b):
        assert bool(jnp.all(oa == eng_a(prep_a, x[None])[0]))
        assert bool(jnp.all(ob == eng_b(prep_b, x[None])[0]))
        # the two plans really are different programs
        assert not bool(jnp.all(oa == ob))


# --- executor cache behaviour under serving --------------------------------

def test_warmup_trace_and_cache_accounting():
    clear_cache()
    m = fire("f", 8, 16, 4, 8)
    server = HeteroServer(buckets=(1, 4), max_wait_ms=3.0)
    st = server.register("f", [m], None, input_hw=(8, 8))
    assert (st["calls"], st["traces"]) == (2, 2)   # one trace per bucket
    assert cache_stats()["misses"] == 1
    # an equivalent (modules, plans) pair is a compile-cache hit...
    st2 = server.register("f2", [fire("f", 8, 16, 4, 8)], None,
                          input_hw=(8, 8))
    assert cache_stats()["hits"] == 1
    # ...sharing the engine, whose bucket shapes are already traced
    assert st2["traces"] == 2 and st2["calls"] == 4
    with server:
        for x in _images(4, (8, 8), 16):      # one at a time: bucket 1
            server.submit("f", x).result(timeout=60)
    eng = server.stats()["engines"]["f"]
    # warm-up passed device arrays, the live path passes host ones: the
    # first live call of bucket 1 compiled that variant, the rest hit it
    assert eng["traces"] == 3


def test_clear_cache_invalidates_live_server_safely():
    clear_cache()
    mods = [fire("f", 8, 16, 4, 8)]
    params = init_network(mods, jax.random.PRNGKey(0))
    server = HeteroServer(buckets=(1, 4), max_wait_ms=3.0)
    server.register("f", mods, None, params, input_hw=(8, 8))
    imgs = _images(3, (8, 8), 16, seed=4)
    with server:
        before = [server.submit("f", x).result(timeout=60) for x in imgs]
        gen0 = cache_stats()["generation"]
        clear_cache()
        assert cache_stats()["generation"] == gen0 + 1
        assert not server.stats()["engines"]["f"]["current"]
        after = [server.submit("f", x).result(timeout=60) for x in imgs]
    # served through a fresh engine, same bits, no dropped requests
    for b, a in zip(before, after):
        assert bool(jnp.all(a == b))
    snap = server.metrics.snapshot()
    assert snap["recompiles"] == 1 and snap["failed"] == 0
    assert server.stats()["engines"]["f"]["current"]
    assert cache_stats()["misses"] >= 1       # the recompile re-populated


# --- metrics ---------------------------------------------------------------

def test_percentile():
    assert percentile([1.0], 99) == 1.0
    assert percentile(range(1, 101), 50) == pytest.approx(50.5)
    assert percentile(range(1, 101), 99) == pytest.approx(99.01)
    assert percentile([], 50) != percentile([], 50)   # NaN


def test_snapshot_reports_latency_and_throughput():
    server = HeteroServer(buckets=(1, 4), max_wait_ms=3.0)
    server.register("f", [fire("f", 8, 16, 4, 8)], None, input_hw=(8, 8))
    with server:
        futs = [server.submit("f", x) for x in _images(8, (8, 8), 16)]
        for f in futs:
            f.result(timeout=60)
    snap = server.metrics.snapshot()
    assert snap["completed"] == 8
    assert snap["p50_ms"] > 0 and snap["p99_ms"] >= snap["p50_ms"]
