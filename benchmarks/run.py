"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig1   conv-size sweep, FPGA-DHM vs TX2-GPU latency/energy   (paper Fig.1)
  fig4   per-network hetero vs GPU-only energy/latency         (paper Fig.4)
  table1 module-family gains vs the paper's reported numbers   (paper Tab.I)
  beyond beyond-paper budgeted partitioner (all schemes)       (§Perf)
  hetero_exec interpreted vs compiled plan execution, batch 1/8/32, plus
         per-network fused-chain coverage (fraction of FPGA conv nodes
         lowered inside a fused group) as hetero_exec/<net>/fused_coverage
  pipeline monolithic vs stage-pipelined execution and the serving
         in-flight depth sweep (§Pipelining): cost-model overlap bound,
         run_many micro-batch throughput, burst rps at in_flight 1/2/4,
         and the served-rows-bit-match check — the guarded rows assert
         multi-in-flight >= single-in-flight at batch >= 8
  qos    multi-resolution QoS serving (§Serving QoS): per-(network,
         resolution, priority) lane scheduling vs sequential
         per-resolution batch-1 serving, per-priority lane percentiles,
         and the prepared-parameter hot-swap bit-match check — the
         guarded rows assert mixed-resolution batched throughput >=
         the sequential loop on all three networks and that every
         served row matches exactly one parameter generation
  serve  batched multi-plan serving vs sequential baselines    (§Serving):
         serve/<net>/seq_interpreted   per-request us through the oracle
         serve/<net>/seq_compiled      per-request us, engine batch-1 loop
         serve/<net>/batched_burst     us/req + rps;p50_ms;p99_ms;vs_seq;
                                       vs_interp (closed-loop burst)
         serve/<net>/load<m>x          offered-load point at m x batched
                                       capacity: offered_rps;rps;p50;p99
         serve/mixed/batched_burst     all plans resident, interleaved
  faults fault-tolerant serving (§Robustness): deterministic FPGA-fault
         injection against a live mobilenetv2 server — circuit-breaker
         failover to the GPU-only plan and half-open probe recovery
         (faults/<net>/failover: bitmatch/recovered/served_frac floors,
         failover-pause p99 from inter-completion gaps) and queue-bound
         load shedding under injected dispatch latency
         (faults/shed: shed_rate + within_deadline floor — rejects are
         synchronous, admitted rows all resolve)
  replan online re-partitioning (§Replanning): injected FPGA stage
         delays make the live hybrid plan measurably slow; the replanner
         fits the delay from timed batches, re-partitions, and
         hot-migrates mid-stream to the all-GPU plan
         (replan/<net>/migrate: converged/bitmatch/post_speedup floors —
         migration must happen, every row must bit-match its own plan
         generation's oracle, and post-migration latency must not exceed
         pre-migration)
  replicas replica-striped data-parallel serving (§Replica striping):
         the same burst striped over 1/2/4 data-axis replicas of a
         forced multi-device host (replicas/<net>/r<k>: vs_1replica and
         bitmatch floors on r4 — striping must never cost throughput and
         every served row must equal its batch-1 oracle) plus the
         cross-replica straggler backup check (replicas/backup:
         other_replica floor — a stuck dispatch re-runs on a DIFFERENT
         replica, bit-matched); needs XLA_FLAGS to force >= 4 devices
  frontend HTTP front-door serving (§Front door): an open-loop offered-
         load sweep through the asyncio door over a live server — real
         sockets, admission control, typed wire errors — then a drain
         under load (frontend/door/load<m>x: offered_rps/rps/p50/p99/
         shed_frac; floors: every 200 row bit-matches the batch-1
         oracle, every non-200 is a typed wire error with a stable
         code, and a drain under load answers every in-flight request)
  kernels wall-clock of the kernel reference paths on this host
  roofline per-cell dry-run roofline terms                     (§Roofline)

``python benchmarks/run.py [section ...]`` runs a subset (default: all).
``--json PATH`` additionally dumps rows plus a flat ``metrics`` dict
(every ``key=value`` float in ``derived``) — CI stores this as the
``BENCH_ci.json`` artifact and guards it against ``baseline.json`` with
``check_regression.py``.
"""
from __future__ import annotations

import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np


def fig1_conv_sweep():
    from repro.core import costmodel as cm
    from repro.core.costmodel import ConvSpec
    rows = []
    for k in (1, 3, 5):
        for n in (2, 4, 8, 16, 32, 64):
            spec = ConvSpec("conv", 224, 224, 3, n, k=k)
            g = cm.GPU.op_cost(spec)
            f = cm.FPGA.full_unroll_cost(spec)
            feasible = cm.FPGA.fits_full_unroll(spec)
            rows.append((f"fig1/conv{k}x{k}_n{n}/gpu", g.latency * 1e6,
                         f"energy_mJ={g.energy*1e3:.3f}"))
            rows.append((f"fig1/conv{k}x{k}_n{n}/fpga", f.latency * 1e6,
                         f"energy_mJ={f.energy*1e3:.3f};fits={feasible}"))
    return rows


def fig4_models():
    from repro.core.graph import NETWORKS
    from repro.core.partitioner import partition_network, summarize
    rows = []
    for net, builder in NETWORKS.items():
        mods = builder()
        het = summarize(partition_network(mods, paper_faithful=True))
        rows.append((f"fig4/{net}/gpu_only", het["gpu_only_latency_ms"] * 1e3,
                     f"energy_mJ={het['gpu_only_energy_mJ']:.2f}"))
        rows.append((f"fig4/{net}/hetero", het["latency_ms"] * 1e3,
                     f"energy_mJ={het['energy_mJ']:.2f};"
                     f"gain={het['energy_gain']:.2f}x;"
                     f"speedup={het['speedup']:.2f}x"))
    return rows


PAPER_TABLE1 = {
    "squeezenet": (1.34, 1.01),
    "mobilenetv2": (1.55, 1.26),
    "shufflenetv2": (1.39, 1.35),
}


def table1_gains():
    from repro.core import costmodel as cm
    from repro.core.graph import NETWORKS
    from repro.core.partitioner import PAPER_SCHEMES, candidates
    rows = []
    for net, builder in NETWORKS.items():
        es, ls = [], []
        for m in builder():
            if m.kind in ("stem", "head"):
                continue
            cands = [p for p in candidates(m)
                     if p.scheme in PAPER_SCHEMES.get(m.kind, ())
                     and p.res.macs <= cm.FPGA.mac_budget]
            if not cands:
                continue
            best = min(cands, key=lambda p: p.cost.energy * p.cost.latency)
            es.append(best.energy_gain)
            ls.append(best.speedup)
        e, l = sum(es) / len(es), sum(ls) / len(ls)
        pe, pl = PAPER_TABLE1[net]
        rows.append((f"table1/{net}", 0.0,
                     f"energy_gain={e:.2f}x(paper={pe});"
                     f"speedup={l:.2f}x(paper={pl})"))
    return rows


def beyond_paper():
    from repro.core.graph import NETWORKS
    from repro.core.partitioner import partition_network, summarize
    rows = []
    for net, builder in NETWORKS.items():
        s = summarize(partition_network(builder(), objective="edp"))
        rows.append((f"beyond/{net}", s["latency_ms"] * 1e3,
                     f"energy_gain={s['energy_gain']:.2f}x;"
                     f"speedup={s['speedup']:.2f}x"))
    return rows


def _time(fn, *args, reps=5):
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps * 1e6


def hetero_exec_rows(batches=(1, 8, 32), res=96):
    """The engine's reason to exist: the same (modules, plans) pair through
    the unjitted per-node interpreter vs the jit-once compiled executor
    (weights quantized at compile time, fused/int8 kernel routing)."""
    from repro.core.executor import compile_network
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network, run_network
    from repro.core.partitioner import fused_chain_coverage, partition_network
    rows = []
    for net, builder in NETWORKS.items():
        mods = builder()
        plans = partition_network(mods, paper_faithful=True)
        params = init_network(mods, jax.random.PRNGKey(0))
        engine = compile_network(mods, plans)
        prepared = engine.prepare(params)
        cov = fused_chain_coverage(mods, plans)
        rows.append((f"hetero_exec/{net}/fused_coverage", 0.0,
                     f"coverage={cov['coverage']:.3f};"
                     f"fpga_nodes={cov['fpga_nodes']};"
                     f"fused_nodes={cov['fused_nodes']}"))
        for b in batches:
            x = jax.random.normal(jax.random.PRNGKey(1), (b, res, res, 3))
            t_i = _time(lambda: run_network(mods, params, x, plans), reps=2)
            t_c = _time(lambda: engine(prepared, x), reps=5)
            rows.append((f"hetero_exec/{net}/b{b}/interpreted", t_i,
                         f"res={res}"))
            rows.append((f"hetero_exec/{net}/b{b}/compiled", t_c,
                         f"res={res};speedup={t_i / t_c:.1f}x"))
    return rows


def _serve_setup(res):
    from repro.core.executor import compile_network
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network
    nets = {}
    for net, builder in NETWORKS.items():
        mods = builder()
        plans = partition_network(mods, paper_faithful=True)
        params = init_network(mods, jax.random.PRNGKey(0))
        eng = compile_network(mods, plans)
        prep = eng.prepare(params)
        jax.block_until_ready(eng(prep, jnp.zeros((1, res, res, 3))))
        # per-network bucket policy: SqueezeNet is all fp32-GEMM compute and
        # goes cache-bound past batch 8 on small hosts; the depthwise nets
        # keep batching gains through 32
        buckets = (1, 4, 8) if net == "squeezenet" else (1, 4, 8, 32)
        nets[net] = dict(mods=mods, plans=plans, params=params, eng=eng,
                         prep=prep, buckets=buckets)
    return nets


def _burst(server, reqs, timeout=300):
    """Submit (net, img) pairs as fast as possible; returns (wall_s,
    per-request latencies).  Latency is stamped by a done-callback (fires
    in the drain thread at result time) — polling result() in submit order
    would bill early finishers for the poll loop's position."""
    t0 = time.perf_counter()
    lats = []
    subs = []
    for net, x in reqs:
        t_sub = time.perf_counter()
        f = server.submit(net, x)
        f.add_done_callback(
            lambda _f, t=t_sub: lats.append(time.perf_counter() - t))
        subs.append(f)
    for f in subs:
        f.result(timeout=timeout)
    return time.perf_counter() - t0, lats


def serve_rows(n_req=32, res=96):
    """Batched async serving vs the sequential interpreted / compiled
    baselines, plus an offered-load sweep (open loop, paced arrivals)."""
    from repro.core.hetero import run_network
    from repro.serving import HeteroServer, percentile
    nets = _serve_setup(res)
    rows = []
    seq_total = 0.0
    for net, d in nets.items():
        imgs = [jax.random.normal(jax.random.PRNGKey(i), (res, res, 3))
                for i in range(n_req)]
        # sequential interpreted oracle (1 warm + 2 timed calls: it's slow)
        t_i = _time(lambda: run_network(d["mods"], d["params"],
                                        imgs[0][None], d["plans"]), reps=2)
        # sequential compiled: engine batch-1 loop, one dispatch per request
        t0 = time.perf_counter()
        for x in imgs:
            jax.block_until_ready(d["eng"](d["prep"], x[None]))
        t_c = (time.perf_counter() - t0) / n_req * 1e6
        seq_total += t_c
        # batched burst through a server with this net's bucket policy
        server = HeteroServer(buckets=d["buckets"], max_wait_ms=2.0)
        server.register(net, d["mods"], d["plans"], d["params"],
                        input_hw=(res, res), buckets=d["buckets"])
        with server:
            _burst(server, [(net, x) for x in imgs[:d["buckets"][-1]]])
            wall, lats = _burst(server, [(net, x) for x in imgs])
            wall2, lats2 = _burst(server, [(net, x) for x in imgs])
            if wall2 < wall:
                wall, lats = wall2, lats2
        t_b = wall / n_req * 1e6
        snap = server.metrics.snapshot()
        rows.append((f"serve/{net}/seq_interpreted", t_i,
                     f"rps={1e6 / t_i:.1f}"))
        rows.append((f"serve/{net}/seq_compiled", t_c,
                     f"rps={1e6 / t_c:.1f}"))
        rows.append((f"serve/{net}/batched_burst", t_b,
                     f"rps={1e6 / t_b:.1f};"
                     f"p50_ms={percentile(lats, 50) * 1e3:.2f};"
                     f"p99_ms={percentile(lats, 99) * 1e3:.2f};"
                     f"batches={snap['batches']};"
                     f"vs_seq={t_c / t_b:.2f}x;vs_interp={t_i / t_b:.2f}x"))
        # offered-load sweep: open loop at 0.5x / 0.9x of burst capacity
        cap_rps = 1e6 / t_b
        for mult in (0.5, 0.9):
            interval = 1.0 / (cap_rps * mult)
            server = HeteroServer(buckets=d["buckets"], max_wait_ms=2.0)
            server.register(net, d["mods"], d["plans"], d["params"],
                            input_hw=(res, res), buckets=d["buckets"])
            with server:
                t0 = time.perf_counter()
                subs = []
                lats = []
                for i, x in enumerate(imgs):
                    target = t0 + i * interval
                    while time.perf_counter() < target:
                        time.sleep(0)
                    t_sub = time.perf_counter()
                    f = server.submit(net, x)
                    f.add_done_callback(
                        lambda _f, t=t_sub:
                        lats.append(time.perf_counter() - t))
                    subs.append(f)
                for f in subs:
                    f.result(timeout=300)
                wall = time.perf_counter() - t0
            rows.append((f"serve/{net}/load{mult}x", wall / n_req * 1e6,
                         f"offered_rps={cap_rps * mult:.1f};"
                         f"rps={n_req / wall:.1f};"
                         f"p50_ms={percentile(lats, 50) * 1e3:.2f};"
                         f"p99_ms={percentile(lats, 99) * 1e3:.2f}"))
    # mixed multi-plan: every network resident, interleaved burst
    server = HeteroServer(buckets=(1, 4, 8, 32), max_wait_ms=2.0)
    for net, d in nets.items():
        server.register(net, d["mods"], d["plans"], d["params"],
                        input_hw=(res, res), buckets=d["buckets"])
    per_net = max(1, n_req // len(nets))
    reqs = [(net, jax.random.normal(jax.random.PRNGKey(100 + i),
                                    (res, res, 3)))
            for i in range(per_net) for net in nets]
    with server:
        _burst(server, reqs[:8])
        wall, lats = _burst(server, reqs)
        wall2, lats2 = _burst(server, reqs)
        if wall2 < wall:
            wall, lats = wall2, lats2
    t_mix = wall / len(reqs) * 1e6
    t_seq_mix = seq_total / len(nets)     # mean sequential-compiled us/req
    rows.append(("serve/mixed/batched_burst", t_mix,
                 f"rps={1e6 / t_mix:.1f};"
                 f"p50_ms={percentile(lats, 50) * 1e3:.2f};"
                 f"p99_ms={percentile(lats, 99) * 1e3:.2f};"
                 f"vs_seq={t_seq_mix / t_mix:.2f}x"))
    return rows


def qos_rows(n_req=48, res_list=(32, 48)):
    """Multi-resolution QoS serving: every (network, resolution, priority)
    triple is its own batching lane, so one server multiplexes input
    shapes the way real deployments do (fixed accelerator config, varying
    request shapes).  Rows per network:

      qos/<net>/seq_perres       sequential batch-1 engine loop over the
                                 same mixed-resolution stream (us/req)
      qos/<net>/mixed_res_burst  batched mixed-resolution + mixed-priority
                                 burst (best of 3): us/req + rps, overall
                                 and per-priority-lane p50/p99, and
                                 vs_seq — guarded at an absolute floor of
                                 1.0 on ALL three networks (batching must
                                 never lose to the sequential loop)
      qos/<net>/hotswap          swap_params mid-stream: shadow-prepare
                                 wall time + the bit-match invariant
                                 (every served row — across resolutions,
                                 priorities, and the swap — equals a
                                 batch-1 engine call under exactly one
                                 parameter generation; rows submitted
                                 after the swap returned match the new
                                 generation; floor bitmatch = 1)
    """
    from repro.core.executor import compile_network
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network
    from repro.serving import HeteroServer, percentile
    rows = []
    buckets = (1, 4, 8)

    def qos_burst(server, net, reqs):
        """Closed-loop burst keeping (x, priority, future) for bit-checks."""
        t0 = time.perf_counter()
        lats, futs = [], []
        for x, prio in reqs:
            t_sub = time.perf_counter()
            f = server.submit(net, x, priority=prio)
            f.add_done_callback(
                lambda _f, t=t_sub: lats.append(time.perf_counter() - t))
            futs.append(f)
        outs = [f.result(timeout=300) for f in futs]
        return time.perf_counter() - t0, lats, outs

    for net, builder in NETWORKS.items():
        mods = builder()
        plans = partition_network(mods, paper_faithful=True)
        params_a = init_network(mods, jax.random.PRNGKey(0))
        params_b = init_network(mods, jax.random.PRNGKey(7))
        eng = compile_network(mods, plans)
        prep_a, prep_b = eng.prepare(params_a), eng.prepare(params_b)
        # interleaved mixed-resolution stream, every 4th request urgent
        reqs = [(jax.random.normal(jax.random.PRNGKey(i),
                                   (res_list[i % len(res_list)],
                                    res_list[i % len(res_list)], 3)),
                 0 if i % 4 == 0 else 1)
                for i in range(n_req)]
        for r in res_list:                 # warm the batch-1 shapes
            jax.block_until_ready(eng(prep_a, jnp.zeros((1, r, r, 3))))
        t0 = time.perf_counter()
        for x, _prio in reqs:              # sequential per-resolution loop
            jax.block_until_ready(eng(prep_a, x[None]))
        t_seq = (time.perf_counter() - t0) / n_req * 1e6
        server = HeteroServer(buckets=buckets, max_wait_ms=2.0, in_flight=2)
        server.register(net, mods, plans, params_a,
                        input_hw=[(r, r) for r in res_list], buckets=buckets)
        with server:
            qos_burst(server, net, reqs[:8])          # warm the live path
            wall, lats, outs = qos_burst(server, net, reqs)
            for _ in range(2):                        # best of 3 bursts
                w2, l2, o2 = qos_burst(server, net, reqs)
                if w2 < wall:
                    wall, lats, outs = w2, l2, o2
            # per-lane percentiles snapshotted HERE so they describe the
            # burst phase, not the hot-swap traffic below
            burst_lanes = server.metrics.snapshot()["lanes"]
            match = all(
                bool((out == eng(prep_a, x[None])[0]).all())
                for (x, _p), out in zip(reqs, outs))
            # hot-swap mid-stream: first half rides the old generation,
            # the swap lands without draining, second half must serve
            # the new one
            pre = [server.submit(net, x, priority=p)
                   for x, p in reqs[:n_req // 2]]
            t_swap = time.perf_counter()
            server.swap_params(net, params_b)
            swap_ms = (time.perf_counter() - t_swap) * 1e3
            post = [server.submit(net, x, priority=p)
                    for x, p in reqs[n_req // 2:]]
            pre_outs = [f.result(timeout=300) for f in pre]
            post_outs = [f.result(timeout=300) for f in post]
            for (x, _p), out in zip(reqs, pre_outs):  # old OR new, never mixed
                match &= (bool((out == eng(prep_a, x[None])[0]).all())
                          or bool((out == eng(prep_b, x[None])[0]).all()))
            for (x, _p), out in zip(reqs[n_req // 2:], post_outs):
                match &= bool((out == eng(prep_b, x[None])[0]).all())
        t_b = wall / n_req * 1e6
        snap = server.metrics.snapshot()
        lane_p99 = {0: [], 1: []}
        for label, st in burst_lanes.items():
            lane_p99[int(label.rsplit("/p", 1)[1])].append(st["p99_ms"])
        res_tag = "x".join(str(r) for r in res_list)   # comma-free CSV
        rows.append((f"qos/{net}/seq_perres", t_seq,
                     f"rps={1e6 / t_seq:.1f};res={res_tag}"))
        rows.append((f"qos/{net}/mixed_res_burst", t_b,
                     f"rps={1e6 / t_b:.1f};"
                     f"p50_ms={percentile(lats, 50) * 1e3:.2f};"
                     f"p99_ms={percentile(lats, 99) * 1e3:.2f};"
                     f"hi_p99_ms={max(lane_p99[0] or [0.0]):.2f};"
                     f"bulk_p99_ms={max(lane_p99[1] or [0.0]):.2f};"
                     f"vs_seq={t_seq / t_b:.2f}x"))
        rows.append((f"qos/{net}/hotswap", swap_ms * 1e3,
                     f"swap_ms={swap_ms:.1f};swaps={snap['swaps']};"
                     f"bitmatch={1.0 if match else 0.0}"))
    return rows


def pipeline_rows(n_req=96, res=32, batch=8):
    """The paper's overlap argument, made measurable: monolithic vs
    stage-pipelined engine execution, and single- vs multi-in-flight
    serving.  The sweep runs at res 32 / batch 8 deliberately — the
    small-feature-map regime where per-op parallelism cannot hide dispatch
    gaps, so keeping k batches in flight is what fills the hardware (at
    large maps XLA already saturates the host and every depth measures the
    same compute).  Each depth is scored by its BEST of 5 alternating
    bursts: host noise only ever slows a burst down, so best-of-n
    estimates capability and the structural gap shows through jitter that
    would whipsaw a median.  Rows:

      pipeline/<net>/model           cost-model stage count + overlap bound
      pipeline/<net>/stage_engine_b8 run_many depth-4 vs serialized
                                     monolithic micro-batches (us/batch)
      pipeline/<net>/serve_if<k>     best-burst rps at in-flight depth k
      pipeline/<net>/inflight        best multi-in-flight vs depth-1
                                     (speedup>=1 guarded in baseline.json
                                     for the depthwise nets; SqueezeNet is
                                     fp32-GEMM cache-bound and stays
                                     informational, like its bucket cap)
                                     + served-row bit-match vs batch-1
                                     monolithic calls (bitmatch=1.0)
    """
    from repro.core.executor import compile_network, compile_pipelined
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network, pipelined_summary
    from repro.serving import HeteroServer, percentile
    rows = []
    depths = (1, 2, 4)
    buckets = (1, 4, batch)       # cap at `batch`: the sweep's batch size
    for net, builder in NETWORKS.items():
        mods = builder()
        plans = partition_network(mods, paper_faithful=True)
        params = init_network(mods, jax.random.PRNGKey(0))
        mono = compile_network(mods, plans)
        prep = mono.prepare(params)
        pipe = compile_pipelined(mods, plans)
        est = pipelined_summary(mods, plans)
        rows.append((f"pipeline/{net}/model", 0.0,
                     f"stages={est['n_stages']};"
                     f"overlap_speedup={est['overlap_speedup']:.2f};"
                     f"steady_ms={est['steady_ms_per_input']:.2f}"))
        # stage engine: 8 micro-batches, serialized monolithic (block per
        # batch) vs depth-4 pipelined dispatch
        xs = [jax.random.normal(jax.random.PRNGKey(i), (batch, res, res, 3))
              for i in range(8)]
        jax.block_until_ready(mono(prep, xs[0]))
        jax.block_until_ready(pipe(prep, xs[0]))

        def mono_sweep():
            for x in xs:
                jax.block_until_ready(mono(prep, x))

        def pipe_sweep():
            for o in pipe.run_many(prep, xs, depth=4):
                jax.block_until_ready(o)

        t_mono = min(_time(mono_sweep, reps=2) for _ in range(2)) / len(xs)
        t_pipe = min(_time(pipe_sweep, reps=2) for _ in range(2)) / len(xs)
        rows.append((f"pipeline/{net}/stage_engine_b{batch}", t_pipe,
                     f"mono_us={t_mono:.1f};vs_mono={t_mono / t_pipe:.2f}x;"
                     f"stages={len(pipe.stages)}"))
        # serving: in-flight depth sweep.  One live server per depth; the
        # five timed bursts ALTERNATE across depths so host-load drift
        # hits every depth equally, and each depth's best burst is scored.
        imgs = [jax.random.normal(jax.random.PRNGKey(100 + i),
                                  (res, res, 3)) for i in range(n_req)]
        reqs = [(net, x) for x in imgs]
        servers, walls, lat_best = {}, {}, {}
        for infl in depths:
            s = HeteroServer(buckets=buckets, max_wait_ms=2.0,
                             in_flight=infl)
            s.register(net, mods, plans, params, input_hw=(res, res),
                       buckets=buckets)
            s.start()
            _burst(s, reqs[:batch])              # warm the live path
            servers[infl], walls[infl] = s, []
        for _round in range(5):
            for infl in depths:
                wall, lats = _burst(servers[infl], reqs)
                walls[infl].append(wall)
                if wall <= min(walls[infl]):
                    lat_best[infl] = lats
        rps = {}
        for infl in depths:
            wall = min(walls[infl])              # best burst (capability)
            rps[infl] = n_req / wall
            lats = lat_best[infl]
            rows.append((f"pipeline/{net}/serve_if{infl}",
                         wall / n_req * 1e6,
                         f"rps={rps[infl]:.1f};"
                         f"p50_ms={percentile(lats, 50) * 1e3:.2f};"
                         f"p99_ms={percentile(lats, 99) * 1e3:.2f}"))
        # served rows must still bit-match batch-1 monolithic calls
        deep = servers[depths[-1]]
        futs = [deep.submit(net, x) for x in imgs[:8]]
        outs = [f.result(timeout=300) for f in futs]
        match = all(bool((out == mono(prep, x[None])[0]).all())
                    for x, out in zip(imgs, outs))
        for s in servers.values():
            s.shutdown()
        best = max(rps[k] for k in depths if k > 1)
        rows.append((f"pipeline/{net}/inflight", 0.0,
                     f"speedup={best / rps[1]:.3f};"
                     f"bitmatch={1.0 if match else 0.0}"))
    return rows


def faults_rows(res=32, n_req=48):
    """Fault-tolerant serving under deterministic injection (§Robustness).

      faults/<net>/failover   a paced request stream rides through injected
                              FPGA dispatch failures: the breaker trips,
                              traffic fails over to the shadow-prepared
                              GPU-only plan, half-open probes recover the
                              hybrid plan.  Floors: bitmatch (every served
                              row equals its batch-1 oracle on the plan
                              that served it), recovered (breaker closed
                              by stream end), served_frac (zero lost
                              futures).  pause_p99_ms is the p99 of
                              inter-completion gaps — the failover pause a
                              client would see.
      faults/shed             queue-bound load shedding under injected
                              dispatch latency: rejects raise synchronous
                              ``Overloaded``.  Floor: within_deadline
                              (every shed raised in < 50 ms AND every
                              admitted request resolved).
    """
    from repro.core.executor import compile_network
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network
    from repro.runtime.faults import FaultPlan, FaultRule, inject
    from repro.serving import HeteroServer, Overloaded, percentile
    rows = []
    net = "mobilenetv2"
    mods = NETWORKS[net]()
    plans = partition_network(mods, paper_faithful=True)
    params = init_network(mods, jax.random.PRNGKey(0))
    imgs = [jax.random.normal(jax.random.PRNGKey(i), (res, res, 3))
            for i in range(n_req)]
    # oracles computed OUTSIDE the inject scope (the injection point is
    # process-global, like the engine cache)
    hybrid = compile_network(mods, plans)
    h_prep = hybrid.prepare(params)
    gpu = compile_network(mods, None)
    g_prep = gpu.prepare(params)
    refs_h = [hybrid(h_prep, x[None])[0] for x in imgs]
    refs_g = [gpu(g_prep, x[None])[0] for x in imgs]

    server = HeteroServer(buckets=(1, 4, 8), max_wait_ms=2.0,
                          breaker_threshold=2, probe_interval_s=0.03,
                          recover_after=1)
    # prewarm: the pause metric should measure the redirect + retry, not
    # a first-failure fallback compile
    server.register(net, mods, plans, params, input_hw=(res, res),
                    prewarm_fallback=True)
    done_t = []
    # 8 clean dispatches, then 3 FPGA faults: two trip the breaker
    # (threshold=2, the first burns the rows' retry budget-free failover),
    # the third fails the first half-open probe; the next probe heals
    plan = FaultPlan([FaultRule(op="dispatch", device="fpga",
                                after=8, times=3)])
    with server:
        with inject(plan):
            futs = []
            for x in imgs:
                f = server.submit(net, x)
                f.add_done_callback(
                    lambda _f: done_t.append(time.perf_counter()))
                futs.append(f)
                time.sleep(0.005)       # paced: probes need wall-clock room
            outs = [f.result(timeout=300) for f in futs]
        recovered = (1.0 if server.stats()["engines"][net]["mode"]
                     == "primary" else 0.0)
    match = all(bool((out == h).all()) or bool((out == g).all())
                for out, h, g in zip(outs, refs_h, refs_g))
    snap = server.metrics.snapshot()
    gaps = [b - a for a, b in zip(sorted(done_t), sorted(done_t)[1:])]
    pause_p99 = percentile(gaps, 99) * 1e3 if gaps else 0.0
    served_frac = snap["completed"] / max(1, snap["submitted"])
    rows.append((f"faults/{net}/failover", pause_p99 * 1e3,
                 f"bitmatch={1.0 if match else 0.0};"
                 f"recovered={recovered};"
                 f"served_frac={served_frac:.3f};"
                 f"pause_p99_ms={pause_p99:.2f};"
                 f"failovers={snap['failovers']};"
                 f"recoveries={snap['recoveries']};"
                 f"retries={snap['retries']};"
                 f"injected={len(plan.fired)}"))

    # queue-bound shedding: bucket-1 lane, depth bound 4, +20 ms injected
    # dispatch latency — an unpaced burst must shed, and shed fast
    server = HeteroServer(buckets=(1,), max_wait_ms=1.0, max_queue=4)
    server.register(net, mods, None, input_hw=(res, res))
    shed, shed_lat, admitted = 0, [], []
    with server:
        with inject(FaultPlan([FaultRule(op="dispatch", kind="delay",
                                         delay_s=0.02, times=None)])):
            for x in imgs:
                t_s = time.perf_counter()
                try:
                    admitted.append(server.submit(net, x))
                except Overloaded:
                    shed += 1
                    shed_lat.append(time.perf_counter() - t_s)
            resolved = sum(1 for f in admitted
                           if f.result(timeout=300) is not None)
    within = (1.0 if resolved == len(admitted)
              and all(dt < 0.050 for dt in shed_lat) else 0.0)
    rows.append(("faults/shed", max(shed_lat, default=0.0) * 1e6,
                 f"within_deadline={within};"
                 f"shed_rate={shed / n_req:.3f};"
                 f"shed={shed};admitted={len(admitted)};"
                 f"shed_p99_us={percentile(shed_lat, 99) * 1e6:.0f}"
                 if shed_lat else
                 f"within_deadline={within};shed_rate=0.000;"
                 f"shed=0;admitted={len(admitted)};shed_p99_us=0"))
    return rows


def replan_rows(res=32, rounds_cap=15):
    """Online re-partitioning under live traffic (§Replanning).

      replan/<net>/migrate   the paper-faithful hybrid plan serves a
                             request stream while every FPGA stage pays a
                             deterministic injected 4 ms delay; the
                             replanner fits the inflated coefficients from
                             timed batches, re-partitions, and
                             hot-migrates to the all-GPU plan mid-stream.
                             Floors: converged (the migration happened and
                             landed on the all-GPU plan), bitmatch (every
                             row from a generation-stable round equals the
                             batch-1 oracle of the plan generation that
                             served it), post_speedup (best post-migration
                             round >= best pre-migration round — shedding
                             the injected delay must show up in latency).
    """
    from repro.core.executor import compile_network
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network
    from repro.core.replan import Replanner
    from repro.runtime.faults import FaultPlan, FaultRule, inject
    from repro.serving import HeteroServer
    net = "mobilenetv2"
    mods = NETWORKS[net]()
    plans = partition_network(mods, paper_faithful=True)
    params = init_network(mods, jax.random.PRNGKey(0))
    imgs = [0.5 * jax.random.normal(k, (res, res, 3))
            for k in jax.random.split(jax.random.PRNGKey(1), 8)]
    rep = Replanner(objective="latency", threshold=0.15, patience=2,
                    min_samples=2)
    # buckets=(8,) so each 8-request round is exactly one batch: a round
    # is either fully inside one plan generation or the migration round
    server = HeteroServer(buckets=(8,), max_wait_ms=2.0, replanner=rep,
                          measure_every=1)
    server.register(net, mods, plans, params, input_hw=(res, res),
                    pipelined=True)
    rule = FaultRule(op="stage", kind="delay", device="fpga",
                     delay_s=0.004, times=None)
    trace = []          # (gen_before, gen_after, plans_after, dt, outs)
    with inject(FaultPlan([rule])):
        with server:
            entry = server._entries[net]
            for rnd in range(rounds_cap):
                g0 = entry.plan_generation
                t0 = time.perf_counter()
                outs = [f.result(timeout=300)
                        for f in [server.submit(net, x) for x in imgs]]
                dt = time.perf_counter() - t0
                trace.append((g0, entry.plan_generation,
                              list(entry.plans), dt, outs))
                devs = server.stats()["engines"][net]["devices"]
                if devs == ("gpu",) and rnd >= 3:
                    break
            st = server.stats()
    converged = (1.0 if st["engines"][net]["devices"] == ("gpu",)
                 and st["server"]["replans"] >= 1 else 0.0)
    # per-generation bit-match: oracle engines built and called OUTSIDE
    # the inject scope.  Rounds that migrated mid-flight have no single
    # generation and are excluded (their rows were served, just not
    # attributable to one oracle).
    checked, match = 0, True
    for g0, g1, plans_after, _dt, outs in trace:
        if g0 != g1:
            continue
        oracle = compile_network(mods, plans_after)
        oprep = oracle.prepare(params)
        for x, out in zip(imgs, outs):
            ref = oracle(oprep, jnp.asarray(x)[None])[0]
            match = match and bool((out == ref).all())
            checked += 1
    bitmatch = 1.0 if match and checked else 0.0
    pre = [dt for g0, g1, _p, dt, _o in trace if g0 == g1 == 0]
    post = [dt for g0, g1, _p, dt, _o in trace if g0 == g1 >= 1]
    pre_req = min(pre) / len(imgs) if pre else float("nan")
    post_req = min(post) / len(imgs) if post else float("nan")
    mig_round = next((i for i, (g0, g1, *_r) in enumerate(trace)
                      if g1 > g0), -1)
    fit = st["server"]["fitted"].get(net, {})
    return [(f"replan/{net}/migrate", post_req * 1e6,
             f"converged={converged};bitmatch={bitmatch};"
             f"post_speedup={pre_req / post_req:.2f};"
             f"pre_req_us={pre_req * 1e6:.0f};"
             f"post_req_us={post_req * 1e6:.0f};"
             f"replans={st['server']['replans']};"
             f"measured={st['server']['measured_batches']};"
             f"migration_round={mig_round};rounds={len(trace)};"
             f"checked={checked};"
             f"fit_gpu={fit.get('gpu', 0.0):.2f};"
             f"fit_fpga={fit.get('fpga', 0.0):.2f};"
             f"fit_xfer={fit.get('xfer', 0.0):.2f}")]


def replicas_rows(res=48, n_req=64, counts=(1, 2, 4), rounds=5):
    """Replica-striped data-parallel serving (§Replica striping).

    The striped points need a multi-device host — under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the CI
    multi-device job) every forced CpuDevice carries one replica.  Rows:

      replicas/mobilenetv2/r<k>  best-of-n burst rps serving the SAME
                                 request stream striped over k replicas;
                                 the r4 row carries vs_1replica (guarded
                                 >= 1: striping must never cost
                                 throughput) and bitmatch (guarded == 1:
                                 every served row equals its batch-1
                                 oracle no matter which replica ran it)
      replicas/backup            cross-replica straggler backup: a stuck
                                 primary dispatch re-runs on the
                                 least-outstanding OTHER replica —
                                 other_replica (guarded == 1) asserts it
                                 fired on a different replica AND its
                                 rows bit-match; pause_ms is the watch ->
                                 backup-result wall time
      replicas/unavailable       informational — too few devices to
                                 stripe (single-device local runs)
    """
    from repro.core.executor import ReplicaSet, compile_network
    from repro.core.graph import NETWORKS
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network
    from repro.serving import HeteroServer
    rows = []
    net = "mobilenetv2"
    ndev = len(jax.devices())
    usable = [k for k in counts if k <= ndev]
    if usable != list(counts):
        rows.append(("replicas/unavailable", 0.0,
                     f"devices={ndev};needed={max(counts)};"
                     f"hint=XLA_FLAGS=--xla_force_host_platform_"
                     f"device_count=8"))
    mods = NETWORKS[net]()
    plans = partition_network(mods, paper_faithful=True)
    params = init_network(mods, jax.random.PRNGKey(0))
    imgs = [np.asarray(jax.random.normal(jax.random.PRNGKey(i),
                                         (res, res, 3)))
            for i in range(n_req)]
    eng = compile_network(mods, plans)
    prep = eng.prepare(params)
    refs = [np.asarray(eng(prep, x[None]))[0] for x in imgs]
    thr = {}
    for k in usable:
        server = HeteroServer(buckets=(1, 4, 8), in_flight=2,
                              max_wait_ms=1.0)
        server.register(net, mods, plans, params, input_hw=(res, res),
                        replicas=k)
        with server:
            # untimed warm burst: python/thread/trace warmup must not be
            # billed to the FIRST measured round (best-of-n below scores
            # capability, like the pipeline in-flight sweep)
            for f in [server.submit(net, x) for x in imgs[:16]]:
                f.result(timeout=300)
            outs, best = [], float("inf")
            for r in range(rounds):
                futs = [server.submit(net, x) for x in imgs]
                t0 = time.perf_counter()
                got = [f.result(timeout=300) for f in futs]
                best = min(best, time.perf_counter() - t0)
                outs = outs or got
            snap = server.metrics.snapshot()
        match = all(bool((o == ref).all())
                    for o, ref in zip(outs, refs))
        thr[k] = n_req / best
        derived = (f"rps={thr[k]:.1f};bitmatch={1.0 if match else 0.0};"
                   f"replica_lanes={max(1, len(snap['replicas']))};"
                   f"batches={snap['batches']}")
        if k > 1:
            derived += f";vs_1replica={thr[k] / thr[1]:.3f}"
        rows.append((f"replicas/{net}/r{k}", best / n_req * 1e6, derived))

    # cross-replica straggler backup: drive the watchdog directly (the
    # deterministic idiom from the fault suite) with a never-ready
    # primary — the backup must land on the OTHER replica, bit-matched
    if ndev >= 2:
        class _NeverReady:
            def is_ready(self):
                return False

        server = HeteroServer(buckets=(1, 4), straggler_min_ms=1.0)
        server.register(net, mods, plans, params, input_hw=(res, res),
                        replicas=2)
        entry = server._entries[net]
        for s in range(10):
            entry.monitor.record(s, 0.001)
        xb = imgs[0][None]
        straggler = entry.engine.pick()
        t0 = time.perf_counter()
        out = server._watch(entry, xb, _NeverReady(), entry.engine,
                            entry.prepared, straggler)
        jax.block_until_ready(out)
        pause = time.perf_counter() - t0
        snap = server.metrics.snapshot()
        calls = entry.engine.exec_stats()["replica_calls"]
        ok = (not isinstance(out, _NeverReady)
              and isinstance(entry.engine, ReplicaSet)
              and snap["cross_replica_backups"] == 1
              and calls[1 - straggler] >= 1
              and bool((np.asarray(out)[0] == refs[0]).all()))
        server.shutdown()
        rows.append(("replicas/backup", pause * 1e6,
                     f"other_replica={1.0 if ok else 0.0};"
                     f"pause_ms={pause * 1e3:.2f};"
                     f"straggler_events={snap['straggler_events']}"))
    return rows


def kernel_bench():
    from repro.kernels.flash_attention.ref import attention
    from repro.kernels.fused_block.ref import fused_dw_pw
    from repro.quant import int8_matmul, quantize
    rows = []
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    x = jax.random.normal(ks[0], (4, 56, 56, 48))
    args = (x, 0.2 * jax.random.normal(ks[1], (3, 3, 48)),
            jnp.zeros((48,)), 0.2 * jax.random.normal(ks[2], (48, 96)),
            jnp.zeros((96,)))
    f = jax.jit(fused_dw_pw)
    rows.append(("kernels/fused_block_ref_56x56x48", _time(f, *args),
                 "xla_reference_path"))
    q = jax.random.normal(ks[3], (1, 8, 1024, 64))
    f = jax.jit(attention)
    rows.append(("kernels/attention_ref_1k", _time(f, q, q, q),
                 "xla_reference_path"))
    a = jax.random.normal(ks[4], (512, 512))
    w = jax.random.normal(ks[5], (512, 512))
    aq, s1 = quantize(a)
    wq, s2 = quantize(w, axis=-1)
    f = jax.jit(int8_matmul)
    rows.append(("kernels/int8_matmul_512", _time(f, aq, s1, wq, s2),
                 "int8_path"))
    return rows


def tpu_map_rows():
    """The paper's substrate decision on TPU v5e: fused-Pallas (VMEM
    resident, DHM analogue) vs generic XLA, per module."""
    from repro.core.graph import NETWORKS
    from repro.core.tpu_map import plan_network, summarize
    rows = []
    for net, builder in NETWORKS.items():
        s = summarize(plan_network(builder()))
        rows.append((f"tpu_map/{net}", s["planned_us"],
                     f"generic_us={s['generic_us']:.1f};"
                     f"speedup={s['speedup']:.2f}x;"
                     f"fused={s['fused_modules']}/{s['n_modules']}"))
    return rows


def roofline_rows():
    try:
        from benchmarks.roofline import table
        rows = []
        for t in table():
            if "compute_s" in t:
                rows.append((f"roofline/{t['arch']}/{t['shape']}",
                             t["step_s_lower_bound"] * 1e6,
                             f"bound={t['bound']};"
                             f"roofline_frac={t['roofline_frac']:.3f};"
                             f"useful_frac={t['useful_frac']:.3f}"))
        return rows
    except Exception as e:  # dry-run results absent
        return [("roofline/unavailable", 0.0, f"run dryrun first ({e})")]


def frontend_rows(n_req=48):
    """HTTP front-door serving (§Front door): open-loop offered load
    through real sockets against a live in-process server.

      frontend/door/load<m>x  requests fired at m x the door's measured
                              closed-loop capacity, each on its own
                              client thread (open loop: arrivals don't
                              wait for completions).  Derived: offered
                              vs achieved rps, p50/p99 ms, shed_frac.
                              Floors: bitmatch (every 200 row equals the
                              batch-1 oracle THROUGH the wire) and typed
                              (every non-200 carries a stable wire code
                              with a retryable bit — never a traceback).
      frontend/keepalive      the SAME 2x-offered-load schedule served
                              by a fixed worker pool twice: fresh
                              connection per request vs persistent
                              keep-alive connections.  Floor:
                              vs_reconnect >= 1.0 (best of 3 rounds) —
                              pooling sockets never costs throughput,
                              and on a dial-taxed path it buys some.
      frontend/binary/<net>   one image through BOTH wire framings
                              (JSON-base64 and application/x-tensor)
                              for each zoo network.  Floor: bitmatch
                              (the encodings are interchangeable
                              codecs).  Derived: wire_ratio (binary
                              frame bytes / JSON body bytes).
      frontend/fuzz           a malformed-body volley (bad dtype,
                              truncated base64, shape overflow, negative
                              dims, bad tensor frames, garbage JSON) on
                              one keep-alive socket.  Floor: typed_4xx
                              == 1.0 — zero 500s, and the socket
                              still serves afterwards.
      frontend/drain          POST /drain while a burst is in flight:
                              the fence is immediate, yet every already-
                              admitted request still gets an answer.
                              Floor: resolved (no request lost to the
                              drain) — plus the drain's wall-clock.
    """
    import http.client
    import json as _json
    import queue as _queue
    import threading
    import urllib.error
    import urllib.request

    from repro.core.executor import compile_network
    from repro.core.graph import fire
    from repro.core.hetero import init_network
    from repro.core.partitioner import partition_network
    from repro.frontend import FrontDoor, LocalBackend, ServerThread, wire
    from repro.frontend.worker import build_server
    from repro.serving import percentile

    hw, c = (8, 8), 16
    spec = {"networks": [{"kind": "fire", "name": "tiny", "hw": list(hw),
                          "c_in": c, "squeeze": 4, "expand": 8, "seed": 0}],
            "server": {"max_wait_ms": 1.0}}
    mods = [fire("tiny", hw[0], c, 4, 8)]
    eng = compile_network(mods, partition_network(mods, paper_faithful=True))
    prep = eng.prepare(init_network(mods, jax.random.PRNGKey(0)))
    imgs = [np.asarray(0.5 * jax.random.normal(jax.random.PRNGKey(i),
                                               (*hw, c)), dtype=np.float32)
            for i in range(n_req)]
    refs = [np.asarray(eng(prep, x[None])[0]) for x in imgs]
    bodies = [_json.dumps(wire.infer_payload("tiny", x)).encode()
              for x in imgs]

    def post(port, path, data=b"", timeout=60):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}{path}", data=data, method="POST",
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=timeout) as r:
                return r.status, _json.load(r)
        except urllib.error.HTTPError as e:
            return e.code, _json.load(e)

    def open_loop(port, interval_s):
        """Fire every request on schedule on its own thread (open loop),
        then collect (status, body, latency_s)."""
        out = [None] * len(bodies)
        threads = []

        def one(i):
            t0 = time.perf_counter()
            status, body = post(port, "/v1/infer", bodies[i])
            out[i] = (status, body, time.perf_counter() - t0)

        t_start = time.perf_counter()
        for i in range(len(bodies)):
            while time.perf_counter() - t_start < i * interval_s:
                time.sleep(interval_s / 20)
            th = threading.Thread(target=one, args=(i,))
            th.start()
            threads.append(th)
        for th in threads:
            th.join(120)
        elapsed = time.perf_counter() - t_start
        return out, elapsed

    def judge(results):
        """(bitmatch, typed, ok_lats, n_ok, n_shed) over one sweep."""
        bitmatch, typed, lats, n_ok, n_shed = 1.0, 1.0, [], 0, 0
        for i, r in enumerate(results):
            if r is None:
                typed = 0.0            # a lost request is worse than shed
                continue
            status, body, lat = r
            if status == 200:
                n_ok += 1
                lats.append(lat)
                if not np.array_equal(wire.decode_array(body["result"]),
                                      refs[i]):
                    bitmatch = 0.0
            else:
                n_shed += 1
                if not (isinstance(body, dict) and body.get("error")
                        and "retryable" in body):
                    typed = 0.0
        return bitmatch, typed, lats, n_ok, n_shed

    rows = []
    server = build_server(spec)
    with ServerThread(FrontDoor(LocalBackend(server))) as h:
        # closed-loop capacity probe: one client, back to back
        t0 = time.perf_counter()
        for b in bodies[:12]:
            post(h.port, "/v1/infer", b)
        cap_rps = 12 / (time.perf_counter() - t0)
        for mult in (0.5, 2.0):
            interval = 1.0 / max(1e-6, cap_rps * mult)
            results, elapsed = open_loop(h.port, interval)
            bitmatch, typed, lats, n_ok, n_shed = judge(results)
            us = (np.mean(lats) * 1e6) if lats else 0.0
            rows.append((
                f"frontend/door/load{mult:g}x", us,
                f"bitmatch={bitmatch};typed={typed};"
                f"offered_rps={1.0 / interval:.1f};"
                f"rps={n_ok / elapsed:.1f};"
                f"shed_frac={n_shed / len(results):.3f};"
                f"p50_ms={percentile(lats, 50) * 1e3 if lats else 0:.2f};"
                f"p99_ms={percentile(lats, 99) * 1e3 if lats else 0:.2f}"))

        # keep-alive vs reconnect: the same 2x-offered-load schedule,
        # consumed by a fixed pool of client workers — once dialing a
        # fresh connection per request, once on persistent sockets
        interval = 1.0 / max(1e-6, cap_rps * 2.0)
        n_workers = 8

        def run_mode(keepalive: bool) -> float:
            done = [0] * len(bodies)
            q = _queue.Queue()
            t_start = time.perf_counter()
            for i in range(len(bodies)):
                q.put((i, t_start + i * interval))
            for _ in range(n_workers):
                q.put(None)

            def client():
                conn = (http.client.HTTPConnection(
                    "127.0.0.1", h.port, timeout=60) if keepalive
                    else None)
                while True:
                    item = q.get()
                    if item is None:
                        break
                    i, due = item
                    wait = due - time.perf_counter()
                    if wait > 0:
                        time.sleep(wait)
                    c = conn if keepalive else http.client.HTTPConnection(
                        "127.0.0.1", h.port, timeout=60)
                    try:
                        c.request("POST", "/v1/infer", body=bodies[i],
                                  headers={"Content-Type":
                                           "application/json"})
                        r = c.getresponse()
                        r.read()
                        done[i] = 1 if r.status == 200 else 0
                    except Exception:
                        done[i] = 0
                        if keepalive:       # a dead pooled socket: redial
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", h.port, timeout=60)
                            c = conn
                    finally:
                        if not keepalive:
                            c.close()
                if conn is not None:
                    conn.close()

            threads = [threading.Thread(target=client)
                       for _ in range(n_workers)]
            for th in threads:
                th.start()
            for th in threads:
                th.join(180)
            elapsed = time.perf_counter() - t_start
            return sum(done) / elapsed

        ratios, ka_best, rc_best = [], 0.0, 0.0
        for _round in range(3):             # best of 3: floor-grade signal
            rc = run_mode(keepalive=False)
            ka = run_mode(keepalive=True)
            ka_best, rc_best = max(ka_best, ka), max(rc_best, rc)
            ratios.append(ka / max(1e-9, rc))
        rows.append((
            "frontend/keepalive", 1e6 / max(1e-9, ka_best),
            f"vs_reconnect={max(ratios):.3f};"
            f"keepalive_rps={ka_best:.1f};reconnect_rps={rc_best:.1f}"))

        # malformed-body volley on ONE keep-alive socket: the acceptance
        # bar is zero 500s — every reply a typed 4xx, socket survives
        mal = []
        good = wire.infer_payload("tiny", imgs[0])
        for patch in ({"dtype": "float99"}, {"dtype": "object"},
                      {"shape": "nope"}, {"shape": [-1, 4]},
                      {"shape": [2 ** 31, 2 ** 31]}, {"shape": [1] * 17},
                      {"data": "!!not-base64!!"},
                      {"data": good["data"][:len(good["data"]) // 2]}):
            mal.append((_json.dumps({**good, **patch}).encode(),
                        {"Content-Type": "application/json"}))
        mal.append((b"{garbage", {"Content-Type": "application/json"}))
        mal.append((b"[1,2]", {"Content-Type": "application/json"}))
        mal.append((b"NOPE" + b"\x00" * 12,
                    {"Content-Type": wire.TENSOR_CONTENT_TYPE,
                     "X-Network": "tiny"}))
        mal.append((wire.encode_tensor(imgs[0])[:-3],
                    {"Content-Type": wire.TENSOR_CONTENT_TYPE,
                     "X-Network": "tiny"}))
        n_4xx = n_other = 0
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=30)
        t0 = time.perf_counter()
        for body, headers in mal:
            try:
                conn.request("POST", "/v1/infer", body=body,
                             headers=headers)
                r = conn.getresponse()
                r.read()
                if 400 <= r.status < 500:
                    n_4xx += 1
                else:
                    n_other += 1
            except Exception:
                n_other += 1
                conn = http.client.HTTPConnection("127.0.0.1", h.port,
                                                  timeout=30)
        fuzz_us = (time.perf_counter() - t0) / len(mal) * 1e6
        try:        # the volley must not have burned the socket
            conn.request("POST", "/v1/infer", body=bodies[0],
                         headers={"Content-Type": "application/json"})
            survived = conn.getresponse().status == 200
        except Exception:
            survived = False
        conn.close()
        typed_4xx = 1.0 if (n_other == 0 and n_4xx == len(mal)
                            and survived) else 0.0
        rows.append((
            "frontend/fuzz", fuzz_us,
            f"typed_4xx={typed_4xx};volley={len(mal)};"
            f"n_500={n_other};socket_survived={1.0 if survived else 0.0}"))

        # drain under load: a burst is mid-flight when the fence drops
        results = [None] * 16
        threads = []

        def fire_one(i):
            t0 = time.perf_counter()
            status, body = post(h.port, "/v1/infer", bodies[i])
            results[i] = (status, body, time.perf_counter() - t0)

        for i in range(16):
            th = threading.Thread(target=fire_one, args=(i,))
            th.start()
            threads.append(th)
        time.sleep(0.002)
        t0 = time.perf_counter()
        _status, drain_body = post(h.port, "/drain", b"")
        drain_s = time.perf_counter() - t0
        for th in threads:
            th.join(60)
        bitmatch, typed, _lats, n_ok, n_shed = judge(results)
        resolved = (1.0 if all(r is not None for r in results)
                    and bitmatch and typed else 0.0)
        rows.append((
            "frontend/drain", drain_s * 1e6,
            f"resolved={resolved};drained={1.0 if drain_body.get('drained') else 0.0};"
            f"served={n_ok};typed_rejects={n_shed};"
            f"drain_ms={drain_s * 1e3:.1f}"))

    # binary-framing parity across the whole zoo: the same image through
    # both wire encodings must serve a bit-identical row per network
    zoo = ("mobilenetv2", "squeezenet", "shufflenetv2")
    zoo_spec = {"networks": [{"kind": "zoo", "name": n, "res": [32, 32],
                              "buckets": [1]} for n in zoo],
                "server": {"max_wait_ms": 1.0}}
    zserver = build_server(zoo_spec)
    with ServerThread(FrontDoor(LocalBackend(zserver))) as h:
        conn = http.client.HTTPConnection("127.0.0.1", h.port, timeout=120)

        def ask(net, x, binary):
            body, headers = wire.infer_request(
                net, x, binary=binary,
                accept=wire.TENSOR_CONTENT_TYPE if binary else None)
            t0 = time.perf_counter()
            conn.request("POST", "/v1/infer", body=body, headers=headers)
            r = conn.getresponse()
            raw = r.read()
            dt = time.perf_counter() - t0
            assert r.status == 200, raw[:200]
            row = (wire.decode_tensor(raw) if binary
                   else wire.decode_array(_json.loads(raw)["result"]))
            return row, len(body), dt

        for net in zoo:
            x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                             (32, 32, 3)),
                           dtype=np.float32)
            ask(net, x, binary=False)           # warm the bucket
            row_j, size_j, _ = ask(net, x, binary=False)
            row_b, size_b, t_b = ask(net, x, binary=True)
            bitmatch = 1.0 if (row_j.dtype == row_b.dtype
                               and np.array_equal(row_j, row_b)) else 0.0
            rows.append((
                f"frontend/binary/{net}", t_b * 1e6,
                f"bitmatch={bitmatch};"
                f"wire_ratio={size_b / max(1, size_j):.3f};"
                f"body_bytes={size_b}"))
        conn.close()
    return rows


SECTIONS = {
    "fig1": fig1_conv_sweep,
    "fig4": fig4_models,
    "table1": table1_gains,
    "beyond": beyond_paper,
    "tpu_map": tpu_map_rows,
    "hetero_exec": hetero_exec_rows,
    "serve": serve_rows,
    "qos": qos_rows,
    "pipeline": pipeline_rows,
    "faults": faults_rows,
    "replan": replan_rows,
    "replicas": replicas_rows,
    "frontend": frontend_rows,
    "kernels": kernel_bench,
    "roofline": roofline_rows,
}


def metrics_from_rows(rows) -> dict:
    """Flatten every ``key=value`` float in ``derived`` (trailing 'x'
    stripped) into {"<row>/<key>": value} — the regression-guard input."""
    out = {}
    for name, _us, derived in rows:
        for part in str(derived).split(";"):
            if "=" not in part:
                continue
            k, v = part.split("=", 1)
            try:
                out[f"{name}/{k}"] = float(v.rstrip("x"))
            except ValueError:
                continue
    return out


def main(argv: list[str] | None = None) -> None:
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    args = list(argv if argv is not None else sys.argv[1:])
    json_path = None
    if "--json" in args:
        i = args.index("--json")
        json_path = args[i + 1]
        del args[i:i + 2]
    names = args or list(SECTIONS)
    unknown = [n for n in names if n not in SECTIONS]
    if unknown:
        raise SystemExit(f"unknown section(s) {unknown}; "
                         f"choose from {list(SECTIONS)}")
    print("name,us_per_call,derived")
    all_rows = []
    for n in names:
        for name, us, derived in SECTIONS[n]():
            print(f"{name},{us:.1f},{derived}")
            all_rows.append((name, us, derived))
    if json_path:
        payload = {
            "rows": [{"name": n, "us_per_call": us, "derived": d}
                     for n, us, d in all_rows],
            "metrics": metrics_from_rows(all_rows),
        }
        with open(json_path, "w") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
        print(f"# wrote {json_path} ({len(payload['metrics'])} metrics)",
              file=sys.stderr)


if __name__ == "__main__":
    main()
