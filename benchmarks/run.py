"""Benchmark harness — one benchmark per paper table/figure (§6).

Each prints CSV rows ``bench,mode,metric,value`` measured on CPU with the
tiny per-service model, comparing the three architectures of Fig. 1:
  istio  = per-instance proxy + host routing       (sidecar)
  cilium = one global proxy + host routing         (sidecar-lite)
  xlb    = in-graph admission + batched decode     (this paper)

Run all:      PYTHONPATH=src python -m benchmarks.run
Run a subset: PYTHONPATH=src python -m benchmarks.run table1 fig8
Machine-readable: add ``--json OUT.json`` to dump every emitted row
(``admit`` additionally always writes BENCH_admit.json, the fused-vs-staged
admission trajectory record — see benchmarks/README.md).
"""

from __future__ import annotations

import json
import resource
import sys
import time

import numpy as np

MODES = ("istio", "cilium", "xlb")
ROWS: list[tuple] = []
# --policy NAME reruns the admit sweep under that LB policy (the registry in
# core/policy_defs.py); None = the default least_request measurement that
# BENCH_admit.json and the regression gates track.
_POLICY: str | None = None


def emit(bench, mode, metric, value):
    ROWS.append((bench, mode, metric, value))
    print(f"{bench},{mode},{metric},{value:.4f}" if isinstance(value, float)
          else f"{bench},{mode},{metric},{value}", flush=True)


# --------------------------------------------------------------------------- #


def bench_table1():
    """Table 1: throughput + latency, 1 service × 2 instances."""
    from benchmarks import common
    for mode in MODES:
        r = common.run_closed_loop(mode, n_requests=96, n_instances=2,
                                   slots=16, tokens_per_req=4,
                                   arrivals_per_tick=16)
        emit("table1", mode, "req_per_s", r["req_per_s"])
        emit("table1", mode, "avg_ms", r["avg_ms"])
        emit("table1", mode, "p99_ms", r["p99_ms"])


def bench_fig5():
    """Fig 5: scaling concurrent connections (= live slots)."""
    from benchmarks import common
    for conc in (8, 32, 128):
        for mode in MODES:
            r = common.run_closed_loop(mode, n_requests=4 * conc,
                                       n_instances=2, slots=conc // 2,
                                       tokens_per_req=4,
                                       arrivals_per_tick=conc // 2)
            emit("fig5", mode, f"req_per_s@{conc}", r["req_per_s"])
            emit("fig5", mode, f"p99_ms@{conc}", r["p99_ms"])


def bench_fig6():
    """Fig 6: message size (= tokens per request)."""
    from benchmarks import common
    for toks in (2, 8, 16):
        for mode in MODES:
            r = common.run_closed_loop(mode, n_requests=16, n_instances=2,
                                       slots=8, tokens_per_req=toks)
            emit("fig6", mode, f"req_per_s@{toks}tok", r["req_per_s"])
            emit("fig6", mode, f"avg_ms@{toks}tok", r["avg_ms"])


def bench_fig7():
    """Fig 7: CPU usage at fixed offered load (process CPU-ms per request)."""
    from benchmarks import common
    for mode in MODES:
        cpu0 = resource.getrusage(resource.RUSAGE_SELF).ru_utime
        r = common.run_closed_loop(mode, n_requests=24, n_instances=2,
                                   slots=8, tokens_per_req=4)
        cpu = resource.getrusage(resource.RUSAGE_SELF).ru_utime - cpu0
        emit("fig7", mode, "cpu_ms_per_req", 1e3 * cpu / max(r["completed"], 1))


def bench_fig8():
    """Fig 8: service-chain length 1..9."""
    from benchmarks import common
    for chain in (1, 3, 6, 9):
        for mode in MODES:
            r = common.run_chain(mode, chain_len=chain, n_requests=12)
            emit("fig8", mode, f"req_per_s@len{chain}", r["req_per_s"])
            emit("fig8", mode, f"avg_ms@len{chain}", r["avg_ms"])


def bench_fig9():
    """Fig 9: service density — many fleets on one host."""
    from benchmarks import common
    for n_services in (2, 6, 12):
        for mode in MODES:
            svcs = [common.make_service(mode, 2, 4, 2)
                    for _ in range(n_services)]
            common.warm(*svcs)
            for s in svcs:
                s.submit(list(range(4)))
            t0 = time.perf_counter()
            ticks = 0
            while any(s.busy for s in svcs) and ticks < 500:
                for s in svcs:
                    if s.busy:
                        s.tick()
                ticks += 1
            wall = time.perf_counter() - t0
            total = sum(s.stats.completed for s in svcs)
            emit("fig9", mode, f"req_per_s@{n_services}svc",
                 total / wall if wall else 0.0)


def bench_fig10():
    """Fig 10: interference — monitored service at fixed load while a noisy
    neighbour scales.  For cilium the neighbour SHARES the global proxy
    (same engine); istio/xlb keep per-service engines."""
    from benchmarks import common
    for noise in (0, 8, 24):
        for mode in MODES:
            if mode == "cilium":
                # shared proxy: one fleet serves both workloads
                svc = common.warm(
                    common.make_service(mode, 2, 8 + max(4, noise), 4))
                svc.submit(list(range(8)))                   # monitored
                svc.submit(list(range(1000, 1000 + noise)))  # interference
                t0 = time.perf_counter()
                got, ticks = 0, 0
                while got < 8 and ticks < 500:
                    got += sum(1 for r in svc.tick() if r < 1000)
                    ticks += 1
                lat = time.perf_counter() - t0
            else:
                mon = common.make_service(mode, 2, 8, 4)
                noisy = common.make_service(mode, 2, max(4, noise), 4)
                common.warm(mon, noisy)
                mon.submit(list(range(8)))
                noisy.submit(list(range(1000, 1000 + noise)))
                t0 = time.perf_counter()
                got, ticks = 0, 0
                while got < 8 and ticks < 500:
                    got += len(mon.tick())
                    if noisy.busy:
                        noisy.tick()                         # timeshared host
                    ticks += 1
                lat = time.perf_counter() - t0
            emit("fig10", mode, f"mon_latency_ms@noise{noise}", 1e3 * lat)


def bench_fig11():
    """Fig 11: bookinfo application."""
    from benchmarks import common
    from repro.configs import BOOKINFO
    for mode in MODES:
        r = common.run_graph(mode, BOOKINFO, n_requests=8)
        emit("fig11", mode, "req_per_s", r["req_per_s"])
        emit("fig11", mode, "avg_ms", r["avg_ms"])


def bench_fig12():
    """Fig 12: Bank of Anthos application."""
    from benchmarks import common
    from repro.configs import BANK_OF_ANTHOS
    for mode in MODES:
        r = common.run_graph(mode, BANK_OF_ANTHOS, n_requests=8)
        emit("fig12", mode, "req_per_s", r["req_per_s"])
        emit("fig12", mode, "avg_ms", r["avg_ms"])


def _git_commit() -> str:
    import subprocess
    try:
        return subprocess.run(["git", "rev-parse", "--short", "HEAD"],
                              capture_output=True, text=True,
                              timeout=10).stdout.strip() or "unknown"
    except Exception:
        return "unknown"


def _append_trend(bench: str, record: dict) -> None:
    """One timestamped JSONL row per microbench run — BENCH_admit.json /
    BENCH_step.json are overwritten every run, BENCH_TREND.jsonl accumulates
    the per-PR perf history (benchmarks/README.md)."""
    row = {"ts": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
           "commit": _git_commit(), "bench": bench}
    row.update(record)
    with open("BENCH_TREND.jsonl", "a") as f:
        f.write(json.dumps(row) + "\n")
    print("# appended BENCH_TREND.jsonl", flush=True)


def _time_us(fn, *args, reps: int = 30, trials: int = 5) -> float:
    """Median-of-trials per-call latency in µs (robust to noisy-neighbour
    CPU: single-trial numbers on shared runners swing by an order of
    magnitude)."""
    import jax
    out = fn(*args)                                # compile outside timing
    jax.block_until_ready(out)
    ts = []
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) / reps * 1e6)
    ts.sort()
    return ts[len(ts) // 2]


_LB_FRACTION: dict = {}


def _measure_lb_fraction() -> dict:
    """Shared table2/step measurement: fused admit+commit kernel time vs the
    staged jnp chain vs a full engine tick (decode included).  Memoized per
    process — a full bench run hits this from both table2 and step, and the
    engine build + loaded ticks cost minutes on the CPU interpreter."""
    if _LB_FRACTION:
        return _LB_FRACTION
    import jax
    import jax.numpy as jnp
    from benchmarks import common
    from repro.core import policies, router
    from repro.core.balancer import PoolState, RequestBatch
    from repro.core.routing_table import MAX_EPS_PER_CLUSTER
    from repro.kernels import ops

    st = common.build_routing(4)
    R = 64
    svc = jnp.zeros((R,), jnp.int32)
    feats = jnp.zeros((R, 8), jnp.int32)
    reqs = RequestBatch(req_id=jnp.arange(R, dtype=jnp.int32), svc=svc,
                        features=feats, token=jnp.full((R,), 3, jnp.int32),
                        msg_bytes=jnp.full((R,), 128, jnp.int32))
    pool = PoolState.init(4, 16)

    @jax.jit
    def lb_fused(st, pool, key):
        kr, kw = jax.random.split(key)
        rnd = jax.random.randint(kr, (R,), 0, 1 << 30, dtype=jnp.int32)
        gum = jax.random.gumbel(kw, (R, MAX_EPS_PER_CLUSTER), jnp.float32)
        res = ops.admit_commit(reqs, st, pool, rnd, gum)
        return res.endpoint, st._replace(ep_load=res.ep_load,
                                         rr_cursor=res.rr_cursor)

    @jax.jit
    def lb_staged(st, svc, feats, key):
        cl = router.match_cluster(st, svc, feats)
        sel, st = policies.select(st, cl, key, feats)
        return sel.endpoint, st

    key = jax.random.PRNGKey(0)
    lb_us = _time_us(lb_fused, st, pool, key)
    lb_staged_us = _time_us(lb_staged, st, svc, feats, key)

    svc_e = common.make_service("xlb", 2, 8, 4)

    def tick(n):
        # keep arrivals flowing so every timed tick pays the full datapath
        # (admit + decode + completion) — an idle engine takes make_jitted's
        # lax.cond skip path and would understate the denominator
        for _ in range(n):
            svc_e.submit(list(range(8)))
            svc_e.tick()
        return jnp.zeros(())
    tick(1)                                                # warm
    step_us = _time_us(tick, 1, reps=20)
    _LB_FRACTION.update(lb_us=lb_us, lb_staged_us=lb_staged_us,
                        step_us=step_us,
                        lb_fraction_pct=100.0 * lb_us / step_us)
    return _LB_FRACTION


def bench_table2():
    """Table 2 analogue: decompose the XLB step — routing/balancing vs model
    decode — showing essential-LB work is a small fraction (paper: ~20%).
    ``route+balance_us`` is the engine's real path (the fused admit+commit
    kernel); the pre-fusion staged jnp chain is kept as
    ``route+balance_staged_us``."""
    m = _measure_lb_fraction()
    emit("table2", "xlb", "route+balance_us", m["lb_us"])
    emit("table2", "xlb", "route+balance_staged_us", m["lb_staged_us"])
    emit("table2", "xlb", "full_step_us", m["step_us"])
    emit("table2", "xlb", "lb_fraction_pct", m["lb_fraction_pct"])


def bench_admit():
    """Admission microbenchmark: fused Pallas kernel vs the staged jnp chain
    (match → select → allocate, three full-batch argsorts), sweeping the
    admission batch.  Always writes BENCH_admit.json (perf trajectory) —
    unless ``--policy`` reruns the sweep under another registry policy, in
    which case only the labelled BENCH_TREND.jsonl row is appended (the
    regression gates keep tracking the default least_request file)."""
    import jax
    import jax.numpy as jnp
    from benchmarks import common
    from repro.core import policies, request_map, router
    from repro.core.balancer import RequestBatch
    from repro.core.routing_table import MAX_EPS_PER_CLUSTER, POLICY_NAMES
    from repro.kernels import ops

    from repro.kernels import tune

    n_instances, slots = 8, 64
    pol_name = _POLICY or "least_request"
    st = common.build_routing(n_instances, POLICY_NAMES[pol_name])
    free = jnp.ones((n_instances, slots), bool)
    record = {"policy": pol_name, "batch": [], "staged_us": [],
              "fused_us": [], "speedup": [], "block_r": [], "fold": []}
    for R in (64, 256, 1024, 4096):
        svc = jnp.zeros((R,), jnp.int32)
        # hash-keyed policies (maglev/affinity) select on the flow id, so
        # their sweep needs key diversity; the default sweep keeps the
        # all-zero features BENCH_admit.json has always recorded
        feats = (jnp.zeros((R, 8), jnp.int32) if _POLICY is None else
                 jax.random.randint(jax.random.PRNGKey(R), (R, 8), 0, 997,
                                    dtype=jnp.int32))
        reqs = RequestBatch(req_id=jnp.arange(R, dtype=jnp.int32), svc=svc,
                            features=feats, token=jnp.zeros((R,), jnp.int32),
                            msg_bytes=jnp.full((R,), 128, jnp.int32))

        @jax.jit
        def staged(st, key):
            cl = router.match_cluster(st, svc, feats)
            sel, st = policies.select(st, cl, key, feats)
            a = request_map.allocate_slots(sel.instance, free)
            return a.slot, st

        @jax.jit
        def fused(st, key):
            kr, kw = jax.random.split(key)
            rnd = jax.random.randint(kr, (R,), 0, 1 << 30, dtype=jnp.int32)
            gum = jax.random.gumbel(kw, (R, MAX_EPS_PER_CLUSTER),
                                    jnp.float32)
            res = ops.admit(reqs, st, free, rnd, gum)
            return res.slot, st._replace(ep_load=res.ep_load,
                                         rr_cursor=res.rr_cursor)

        key = jax.random.PRNGKey(0)
        reps = max(10, 2048 // R)
        times = {}
        for name, fn in (("staged", staged), ("fused", fused)):
            times[name] = _time_us(fn, st, key, reps=reps)
            emit("admit", name, f"us@{R}", times[name])
        emit("admit", "fused", f"speedup@{R}", times["staged"] / times["fused"])
        record["batch"].append(R)
        record["staged_us"].append(round(times["staged"], 2))
        record["fused_us"].append(round(times["fused"], 2))
        record["speedup"].append(round(times["staged"] / times["fused"], 3))
        block_r, fold = tune.plan_admit(R, free.shape)   # the cached plan
        record["block_r"].append(block_r)
        record["fold"].append(fold)
    if _POLICY is None:
        with open("BENCH_admit.json", "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        print("# wrote BENCH_admit.json", flush=True)
    _append_trend("admit", record)


def bench_step():
    """Completion microbenchmark: the fused Pallas completion kernel
    (done detect → load release → rx metrics → slot free,
    kernels/completion.py) vs the staged jnp chain it replaced in
    ``Engine.step``, sweeping the pool — plus the table2 lb-fraction
    re-measurement.  Always writes BENCH_step.json (perf trajectory)."""
    import jax
    import jax.numpy as jnp
    from repro.core import policies, routing_table
    from repro.core.balancer import PoolState
    from repro.kernels import ops

    from repro.kernels import tune

    rstate = routing_table.empty_state()
    eos, max_len = 1, 16
    record = {"pool": [], "staged_us": [], "fused_us": [], "speedup": [],
              "block_i": [], "fold": []}
    for I, C in ((2, 16), (8, 64), (16, 256)):
        ks = jax.random.split(jax.random.PRNGKey(0), 6)
        active = jax.random.bernoulli(ks[0], 0.7, (I, C))
        preq = jnp.where(active, jax.random.randint(ks[1], (I, C), 0, 9999),
                         -1).astype(jnp.int32)
        pep = jnp.where(active, jax.random.randint(ks[2], (I, C), 0, I),
                        -1).astype(jnp.int32)
        psvc = jnp.zeros((I, C), jnp.int32)
        plen = jax.random.randint(ks[3], (I, C), 0, max_len, dtype=jnp.int32)
        ptok = jax.random.randint(ks[4], (I, C), 2, 97, dtype=jnp.int32)
        nxt = jnp.where(jax.random.bernoulli(ks[5], 0.2, (I, C)), eos,
                        7).astype(jnp.int32)
        load = jnp.full_like(rstate.ep_load, 9)
        rx = jnp.zeros((routing_table.MAX_SERVICES,), jnp.int32)

        @jax.jit
        def fused(preq, pep, psvc, plen, ptok, active, nxt, load, rx):
            r = ops.complete(PoolState(preq, pep, psvc, plen, ptok, active),
                             nxt, load, rx, eos=eos, max_len=max_len)
            return (r.pool.req_id, r.pool.endpoint, r.pool.length,
                    r.pool.token, r.pool.active, r.ep_load, r.rx_bytes)

        @jax.jit
        def staged(preq, pep, psvc, plen, ptok, active, nxt, load, rx):
            # the pre-fusion Engine.step completion chain, verbatim
            B = preq.size
            new_len = jnp.where(active, plen + 1, plen)
            done = active & ((nxt == eos) | (new_len >= max_len - 1))
            load = policies.release(
                rstate._replace(ep_load=load), pep.reshape(B),
                done.reshape(B)).ep_load
            rx = rx.at[jnp.maximum(psvc, 0).reshape(B)].add(
                jnp.where(active, 2, 0).reshape(B), mode="drop")
            preq = jnp.where(done, -1, preq)
            pep = jnp.where(done, -1, pep)
            plen = jnp.where(done, 0, new_len)
            ptok = jnp.where(active, nxt, ptok)
            return preq, pep, plen, ptok, active & ~done, load, rx

        args = (preq, pep, psvc, plen, ptok, active, nxt, load, rx)
        times = {}
        for name, fn in (("staged", staged), ("fused", fused)):
            times[name] = _time_us(fn, *args)
            emit("step", name, f"us@{I}x{C}", times[name])
        emit("step", "fused", f"speedup@{I}x{C}",
             times["staged"] / times["fused"])
        record["pool"].append(f"{I}x{C}")
        record["staged_us"].append(round(times["staged"], 2))
        record["fused_us"].append(round(times["fused"], 2))
        record["speedup"].append(round(times["staged"] / times["fused"], 3))
        block_i, fold = tune.plan_complete((I, C))      # the cached plan
        record["block_i"].append(block_i)
        record["fold"].append(fold)

    m = _measure_lb_fraction()                     # ROADMAP target: < 25%
    emit("step", "xlb", "lb_fraction_pct", m["lb_fraction_pct"])
    record["lb_fraction_pct"] = round(m["lb_fraction_pct"], 2)
    record["full_step_us"] = round(m["step_us"], 2)
    with open("BENCH_step.json", "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print("# wrote BENCH_step.json", flush=True)
    _append_trend("step", record)


def bench_degraded():
    """Degraded-operation scenario (DESIGN.md §8): one instance turns 10×
    slower mid-run; the health daemon must detect it through the in-kernel
    latency EWMAs, eject it, hold tail latency at the healthy baseline,
    and — once the fault clears — probe and fully restore it with ZERO
    operator transactions.  A second *graded* leg runs a heterogeneous
    WEIGHTED fleet with ``graded_weights=True``: continuous per-epoch
    demotion, no ejection allowed.  Writes BENCH_degraded.json (both legs
    + their per-epoch timelines) and appends the classic record to
    BENCH_TREND.jsonl."""
    from benchmarks import common
    r = common.run_degraded("xlb")
    for k in ("healthy_p99_ticks", "degraded_p99_ticks",
              "recovered_p99_ticks", "recovery_ratio"):
        emit("degraded", "xlb", k, r[k])
    emit("degraded", "xlb", "eject_tick",
         -1 if r["eject_tick"] is None else r["eject_tick"])
    emit("degraded", "xlb", "uneject_tick",
         -1 if r["uneject_tick"] is None else r["uneject_tick"])
    for k in ("operator_txns", "daemon_txns", "end_drained", "completed",
              "dropped"):
        emit("degraded", "xlb", k, r[k])
    g = common.run_degraded("xlb", graded=True, factor=3)
    emit("degraded", "xlb", "graded_daemon_txns", g["daemon_txns"])
    emit("degraded", "xlb", "graded_min_sick_weight", g["min_sick_weight"])
    emit("degraded", "xlb", "graded_end_weight", g["end_weight"])
    emit("degraded", "xlb", "graded_recovery_ratio", g["recovery_ratio"])
    with open("BENCH_degraded.json", "w") as f:
        json.dump({"classic": r, "graded": g}, f, indent=2)
        f.write("\n")
    print("# wrote BENCH_degraded.json", flush=True)
    _append_trend("degraded", {k: v for k, v in r.items()
                               if k != "timeline"})
    _gate_degraded(r)
    _gate_graded(g)


def _chain_workload(n_requests: int = 24, seed: int = 11,
                    rate: float = 2.0):
    """The canonical chain workload: fixed-seed Poisson arrivals — every
    chain bench / gate / replay test draws this exact request stream."""
    from benchmarks import common
    from repro.workload import PoissonArrivals, Workload
    return Workload(PoissonArrivals(rate=rate, seed=seed),
                    n_requests=n_requests, vocab=common.CFG.vocab)


def bench_chain():
    """The workload-subsystem chain scenario (DESIGN.md §10): a seeded
    Poisson stream through a depth-3 service chain on all three engines,
    end-to-end latency in deterministic engine ticks (submit at hop 0 →
    completion at hop 2), plus an xlb live-ops leg replaying a mid-run
    canary shift and an elastic scale-down/up.  Writes BENCH_chain.json,
    appends schema-validated scenario rows to BENCH_TREND.jsonl (the rows
    experiments/make_report.py renders as SLO tables), and gates xlb's
    chain p99 against both sidecars."""
    from benchmarks import common
    from repro.core.routing_table import POLICY_WEIGHTED
    from repro.workload import Op, append_scenario_row
    depth = 3
    rows = []
    for mode in MODES:
        r = common.run_chain_scenario(mode, depth=depth,
                                      workload=_chain_workload())["row"]
        for k in ("p50_ticks", "p99_ticks", "p999_ticks"):
            emit("chain", mode, k, r[k])
        emit("chain", mode, "completed", r["completed"])
        emit("chain", mode, "ticks", r["ticks"])
        rows.append(r)
    ops = [Op(6, "canary", hop=1, args={"instance": 1, "pct": 75.0}),
           Op(10, "scale", hop=2, args={"target": 1}),
           Op(16, "scale", hop=2, args={"target": 2})]
    live = common.run_chain_scenario("xlb", depth=depth,
                                     workload=_chain_workload(), ops=ops,
                                     policy=POLICY_WEIGHTED,
                                     label="chain_liveops")["row"]
    emit("chain", "xlb", "liveops_p99_ticks", live["p99_ticks"])
    emit("chain", "xlb", "liveops_txns", live["txns"])
    rows.append(live)
    from repro.runtime.serve_loop import Fault, FaultInjector
    graded = common.run_chain_scenario(
        "xlb", depth=depth, n_instances=3, slots=6, policy=POLICY_WEIGHTED,
        health_cfg=_graded_chain_cfg(), epoch_interval=6,
        faults={0: FaultInjector([Fault(0, "slow", factor=3, start=0)])},
        workload=_chain_workload(n_requests=40, seed=7, rate=1.5),
        label="chain_graded")["row"]
    emit("chain", "xlb", "graded_p99_ticks", graded["p99_ticks"])
    emit("chain", "xlb", "graded_health_txns", graded["health_txns"])
    rows.append(graded)
    _gate_chain([r for r in rows if r["scenario"] == "chain"])
    with open("BENCH_chain.json", "w") as f:
        json.dump({"depth": depth, "rows": rows}, f, indent=2)
        f.write("\n")
    print("# wrote BENCH_chain.json", flush=True)
    for r in rows:
        append_scenario_row(r)
    print(f"# appended {len(rows)} scenario rows to BENCH_TREND.jsonl",
          flush=True)


def _gate_chain(rows: list) -> None:
    """The chain SLO gate (ROADMAP): at depth >= 3 the in-graph datapath's
    end-to-end p99 must not exceed either sidecar's — per-hop interposition
    compounds with chain length, and holding even there is the paper's
    central claim.  Tick latencies are deterministic, so this is an exact
    comparison, not a noisy-timer heuristic."""
    by = {r["mode"]: r for r in rows}
    fails = []
    missing = [m for m in MODES if m not in by]
    if missing:
        sys.exit(f"check: chain gate FAILED — no rows for {missing}")
    xlb = by["xlb"]
    if xlb["completed"] < xlb["n_requests"]:
        fails.append(f"xlb completed {xlb['completed']}/"
                     f"{xlb['n_requests']} (stalled or dropped)")
    for side in ("istio", "cilium"):
        if not xlb["p99_ticks"] <= by[side]["p99_ticks"]:   # NaN fails too
            fails.append(f"xlb chain p99 {xlb['p99_ticks']:.1f} ticks > "
                         f"{side} {by[side]['p99_ticks']:.1f} at depth "
                         f"{xlb['depth']}")
    if fails:
        sys.exit("check: chain gate FAILED — " + "; ".join(fails))
    print(f"# check: chain gate OK — depth {xlb['depth']} p99 ticks "
          + " ".join(f"{m}={by[m]['p99_ticks']:.1f}" for m in MODES),
          flush=True)


def check_chain(shards: int = 2) -> None:
    """--check leg for the workload/chain subsystem: the depth-3 seeded
    chain must run to completion on all three engines, replay bit-identical
    under the fixed seed, pass the xlb p99 gate, and drive every hop
    through the mesh-sharded admission datapath (--shards 2, see
    ``_sharded_leg``)."""
    from benchmarks import common
    depth, n_req = 3, 8
    rows = {}
    for mode in MODES:
        r = common.run_chain_scenario(
            mode, depth=depth,
            workload=_chain_workload(n_requests=n_req))["row"]
        if r["completed"] != r["n_requests"]:
            sys.exit(f"check: chain smoke FAILED — {mode} completed "
                     f"{r['completed']}/{r['n_requests']}")
        print(f"# check: chain smoke OK — {mode} {r['completed']}/"
              f"{r['n_requests']} in {r['ticks']} ticks", flush=True)
        rows[mode] = r
    replay = common.run_chain_scenario(
        "xlb", depth=depth,
        workload=_chain_workload(n_requests=n_req))["row"]
    if replay != rows["xlb"]:
        drift = sorted(k for k in replay
                       if replay[k] != rows["xlb"].get(k))
        sys.exit(f"check: chain replay FAILED — scenario row drifted "
                 f"under the same seed on {drift}")
    print("# check: chain replay OK — bit-identical scenario row under "
          "seed 11", flush=True)
    _gate_chain(list(rows.values()))
    _sharded_leg(shards, _sharded_chain_leg, depth, n_req, shards,
                 what="check: sharded chain smoke")
    print(f"# check: sharded chain smoke OK — xlb --shards {shards} "
          f"{n_req}/{n_req}", flush=True)


def _sharded_chain_leg(depth: int, n_req: int, shards: int) -> None:
    """The seeded chain on xlb with every hop on a ``shards``-way mesh;
    every request must complete (a ``_sharded_leg``)."""
    from benchmarks import common
    out = common.run_chain_scenario(
        "xlb", depth=depth, shards=shards,
        workload=_chain_workload(n_requests=n_req))
    if out["row"]["completed"] != n_req:
        sys.exit(f"check: sharded chain smoke FAILED — completed "
                 f"{out['row']['completed']}/{n_req}")


def _gate_degraded(r: dict) -> None:
    """The closed-loop health gate (ROADMAP): after the fault clears the
    loop must have recovered on its own — tail latency back near baseline,
    the sick endpoint re-admitted at full weight, and not a single
    config transaction authored by anything but the daemon."""
    fails = []
    if not r["recovery_ratio"] <= 1.5:       # catches NaN too
        fails.append(f"recovered/healthy p99 {r['recovery_ratio']:.3f} "
                     "> 1.5 (tail latency never recovered)")
    if r["eject_tick"] is None:
        fails.append("sick endpoint was never ejected")
    if r["uneject_tick"] is None:
        fails.append("ejected endpoint never re-admitted after the fault "
                     "cleared")
    if r["end_drained"] != 0:
        fails.append(f"{r['end_drained']} endpoint(s) still drained at end "
                     "of run")
    if r["end_state"] != "closed":
        fails.append(f"breaker ended {r['end_state']!r}, want 'closed'")
    if r["operator_txns"] != 0:
        fails.append(f"{r['operator_txns']} non-daemon config txns — "
                     "recovery was not closed-loop")
    if fails:
        sys.exit("check: degraded-recovery gate FAILED — " +
                 "; ".join(fails))
    print(f"# check: degraded gate OK — eject@{r['eject_tick']} "
          f"uneject@{r['uneject_tick']} ratio {r['recovery_ratio']:.2f} "
          f"(daemon txns {r['daemon_txns']}, operator txns 0)", flush=True)


def check_degraded() -> None:
    """--check leg for the closed health loop: run the degraded scenario
    small and gate on autonomous recovery (run.py --check always
    re-measures this one — it is cheap and fully deterministic, so there
    is no recorded-file staleness to tolerate)."""
    from benchmarks import common
    _gate_degraded(common.run_degraded("xlb"))


def _gate_graded(g: dict) -> None:
    """The graded-weights gate: on a heterogeneous fleet the daemon must
    track latency with continuous weight commits — demoting the sick
    instance well below parity, NEVER tripping the breaker, re-promoting
    once the fault clears — and tail latency must still recover."""
    fails = []
    if g["eject_tick"] is not None:
        fails.append(f"breaker ejected at tick {g['eject_tick']} — graded "
                     "mode must demote, not eject")
    if g["operator_txns"] != 0:
        fails.append(f"{g['operator_txns']} non-daemon config txns")
    if g["daemon_txns"] < 10:
        fails.append(f"only {g['daemon_txns']} daemon txns — graded "
                     "tracking never engaged")
    if g["min_sick_weight"] is None or g["min_sick_weight"] > 0.6:
        fails.append(f"sick instance never demoted below 0.6 "
                     f"(min weight {g['min_sick_weight']})")
    if not g["end_weight"] >= 0.75:          # catches NaN too
        fails.append(f"sick instance not re-promoted after the fault "
                     f"(end weight {g['end_weight']:.3f} < 0.75)")
    if g["end_drained"] != 0:
        fails.append(f"{g['end_drained']} endpoint(s) drained — graded "
                     "mode must keep the whole fleet serving")
    if not g["recovery_ratio"] <= 1.5:
        fails.append(f"recovered/healthy p99 {g['recovery_ratio']:.3f} "
                     "> 1.5")
    if fails:
        sys.exit("check: graded-weights gate FAILED — " + "; ".join(fails))
    print(f"# check: graded gate OK — min sick weight "
          f"{g['min_sick_weight']:.2f}, end weight {g['end_weight']:.2f}, "
          f"{g['daemon_txns']} daemon txns, no ejection", flush=True)


def _graded_chain_cfg():
    from repro.core.health import HealthConfig
    return HealthConfig(k_eject=12.0, trip_after=8, cooldown=10,
                        recover_after=2, probe_patience=10,
                        graded_weights=True)


def check_graded() -> None:
    """--check leg for graded weights (heterogeneous fleets): the degraded
    graded leg must pass ``_gate_graded``, and a depth-2 chain with a
    permanently-slow hop-0 instance under per-hop HealthPolicy daemons
    must complete with the graded tracking engaged (weight commits that
    demote below parity)."""
    from benchmarks import common
    from repro.core.routing_table import POLICY_WEIGHTED
    from repro.runtime.serve_loop import Fault, FaultInjector
    _gate_graded(common.run_degraded("xlb", graded=True, factor=3))
    out = common.run_chain_scenario(
        "xlb", depth=2, n_instances=3, slots=6, policy=POLICY_WEIGHTED,
        health_cfg=_graded_chain_cfg(), epoch_interval=6,
        faults={0: FaultInjector([Fault(0, "slow", factor=3, start=0)])},
        workload=_chain_workload(n_requests=40, seed=7, rate=1.5),
        label="chain_graded")
    row = out["row"]
    fails = []
    if row["completed"] != row["n_requests"]:
        fails.append(f"completed {row['completed']}/{row['n_requests']}")
    if row["health_txns"] < 2:
        fails.append(f"per-hop health daemons committed "
                     f"{row['health_txns']} txns — tracking never engaged")
    ws = [w for hop in row["end_weights"] for w in hop if w is not None]
    if not ws or min(ws) > 0.9:
        fails.append(f"graded weights never demoted any endpoint "
                     f"(min end weight {min(ws) if ws else None})")
    if fails:
        sys.exit("check: graded chain gate FAILED — " + "; ".join(fails))
    print(f"# check: graded chain OK — {row['health_txns']} health txns, "
          f"min end weight {min(ws):.2f}", flush=True)


def _gate_chaos(out: dict, base: dict) -> None:
    """The chaos convergence + SLO-recovery gate (DESIGN.md §11): after
    the schedule ends, every live consumer must hold a bit-exact copy of
    the control plane's RoutingState at the head version with a monotone
    no-lost-bump history; the crashed consumer rejoined with at most one
    snapshot resync; every request completed; and the recovered-window
    p99 is within 1.5× of the identical run over a fault-free channel."""
    row, rep, brow = out["row"], out["report"], base["row"]
    fails = []
    if not row["converged"] or rep["issues"]:
        fails.append("transport did not converge: "
                     + "; ".join(rep["issues"]))
    if row["crashes"] != 1:
        fails.append(f"{row['crashes']} consumer crashes, schedule has "
                     "exactly 1")
    if row["resyncs"] > row["crashes"]:
        fails.append(f"{row['resyncs']} resyncs for {row['crashes']} "
                     "crash(es) — more than one resync per crash")
    if not (brow["converged"] and brow["crashes"] == 0
            and brow["resyncs"] == 0):
        fails.append("fault-free baseline leg was not clean")
    if row["completed"] != row["n_requests"] or row["dropped"]:
        fails.append(f"completed {row['completed']}/{row['n_requests']}, "
                     f"dropped {row['dropped']}")
    lim = 1.5 * brow["recovered_p99_ticks"]
    if not row["recovered_p99_ticks"] <= lim:      # NaN fails too
        fails.append(f"post-recovery p99 {row['recovered_p99_ticks']} "
                     f"ticks > 1.5x fault-free {brow['recovered_p99_ticks']}")
    if fails:
        sys.exit("check: chaos gate FAILED — " + "; ".join(fails))
    print(f"# check: chaos gate OK — {row['versions']} versions to "
          f"{row['consumers']} consumers over a lossy channel "
          f"(drop {row['msgs_dropped']}/dup {row['msgs_duped']}/part "
          f"{row['msgs_partitioned']}), {row['resyncs']} resync for "
          f"{row['crashes']} crash, recovered p99 "
          f"{row['recovered_p99_ticks']:.1f} vs baseline "
          f"{brow['recovered_p99_ticks']:.1f}", flush=True)


def bench_chaos():
    """Transport-chaos scenario (DESIGN.md §11): generated load served by
    a RemoteConsumer-attached fleet while the live-ops schedule commits
    config over a lossy, partitioned control channel and a replica
    consumer is crash-restarted mid-canary — plus the identical schedule
    over a fault-free channel (the SLO-recovery baseline).  Writes
    BENCH_chaos.json and appends both validated ``bench="chaos"`` rows to
    BENCH_TREND.jsonl."""
    from benchmarks import common
    from repro.workload import append_scenario_row
    out = common.run_chaos("xlb")
    base = common.run_chaos("xlb", chaos=False)
    row, brow = dict(out["row"]), base["row"]
    for k in ("healthy_p99_ticks", "chaos_p99_ticks",
              "recovered_p99_ticks", "recovery_ratio"):
        emit("chaos", "xlb", k, row[k])
    emit("chaos", "xlb", "baseline_recovered_p99_ticks",
         brow["recovered_p99_ticks"])
    for k in ("versions", "resyncs", "crashes", "flush_ticks",
              "msgs_sent", "msgs_dropped", "msgs_duped", "msgs_delivered",
              "msgs_partitioned", "plan_sends", "snap_sends"):
        emit("chaos", "xlb", k, row[k])
    emit("chaos", "xlb", "converged", int(row["converged"]))
    _gate_chaos(out, base)
    row["baseline_p99_ticks"] = brow["recovered_p99_ticks"]
    with open("BENCH_chaos.json", "w") as f:
        json.dump({"chaos": {"row": row, "report": out["report"],
                             "scenario_log": out["scenario_log"],
                             "histories": out["histories"],
                             "publisher": out["publisher"]},
                   "baseline": {"row": brow}}, f, indent=2)
        f.write("\n")
    print("# wrote BENCH_chaos.json", flush=True)
    for r in (row, brow):
        append_scenario_row(r)
    print("# appended 2 chaos rows to BENCH_TREND.jsonl", flush=True)


def check_chaos() -> None:
    """--check leg for the plan transport: the chaos scenario must pass
    ``_gate_chaos`` AND replay bit-identically — same row, same per-consumer
    apply/resync histories, same channel counters — under the fixed seed."""
    from benchmarks import common
    out = common.run_chaos("xlb")
    base = common.run_chaos("xlb", chaos=False)
    _gate_chaos(out, base)
    replay = common.run_chaos("xlb")
    drift = [k for k in ("row", "histories", "channel")
             if replay[k] != out[k]]
    if drift:
        sys.exit(f"check: chaos replay FAILED — {drift} drifted under "
                 f"seed {out['row']['seed']}")
    print(f"# check: chaos replay OK — bit-identical row, histories and "
          f"channel counters under seed {out['row']['seed']}", flush=True)


def _sharded_leg(shards: int, leg, *args, what: str, timeout: int = 1800):
    """Call ``leg(*args)``, a module-level function that needs ``shards``
    devices, and return its JSON-able result.  A process that already sees
    them (a TPU host with enough chips) calls it in-process: a chip belongs
    to one process, so a child started after this process touched JAX
    would fail or hang.  Only the CPU rehearsal, whose one host device
    cannot be split after start-up, calls the same function by name in a
    child on a virtual host mesh; any other backend short of devices is
    refused."""
    import jax
    n, kind = len(jax.devices()), jax.default_backend()
    if n >= shards:
        return leg(*args)
    if kind != "cpu":
        sys.exit(f"{what} FAILED — needs {shards} devices, this {kind} "
                 f"host has {n} (a child process cannot share the chip)")
    return _run_on_host_mesh(shards, leg, args, what=what, timeout=timeout)


def _run_on_host_mesh(shards: int, leg, args: tuple, *, what: str,
                      timeout: int = 1800):
    """Call ``leg(*args)`` in a python subprocess on an M-device virtual
    host mesh (XLA_FLAGS must precede jax init, and this process already
    booted a 1-device jax) — the CPU rehearsal path of ``_sharded_leg``.
    The child prints the result as JSON on its last stdout line.  Exits
    with the captured output on failure."""
    import os
    import subprocess
    # under ``python -m benchmarks.run`` this module is ``__main__``
    spec = sys.modules[leg.__module__].__spec__
    module = spec.name if spec else leg.__module__
    code = (f"import json; from {module} import {leg.__name__} as leg; "
            f"print(json.dumps(leg(*{tuple(args)!r})), flush=True)")
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={shards}"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=timeout)
    if out.returncode != 0:
        sys.exit(f"{what} FAILED —\n{out.stdout}\n{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def bench_shard():
    """Sharded-admission microbenchmark (ROADMAP scale-out): the mesh-
    sharded datapath (``ops.admit_commit_sharded`` — per-shard fused kernel
    + psum reconciliation + commit relay) vs the single-shard fused kernel
    on the same batch, on an M-way mesh (``benchmarks/shard_bench.py``,
    in-process or on a virtual host mesh — ``_sharded_leg``).  Rows append to
    BENCH_TREND.jsonl; the CPU-interpreter ratio is advisory (M "hosts"
    timeshare one machine) — the real read is the TPU leg."""
    from benchmarks import shard_bench
    shards = 2
    record = _sharded_leg(shards, shard_bench.measure, shards,
                          what="bench_shard worker")
    for b, s1, s2, r in zip(record["batch"], record["single_us"],
                            record["sharded_us"], record["ratio"]):
        emit("shard", "single", f"us@{b}", s1)
        emit("shard", "sharded", f"us@{b}x{shards}", s2)
        emit("shard", "sharded", f"ratio@{b}", r)
    _append_trend("shard", record)


def check_gates(remeasured: bool = False) -> None:
    """Regression gates (ROADMAP): the fused admission kernel must hold
    speedup >= 1.3 over the staged chain at batch >= 256 per the last
    recorded BENCH_admit.json; the fused completion kernel must hold
    fused/staged >= 0.8 at the engine-sized 2x16 pool per BENCH_step.json;
    all three engines must still drive the serving launcher end-to-end
    through the Balancer protocol; the closed health loop must recover
    the degraded scenario autonomously (``check_degraded``) and track
    heterogeneous fleets with graded weights (``check_graded``); and the
    plan transport must converge deterministically under chaos
    (``check_chaos``)."""
    if not remeasured:
        print("# check: gating the last recorded BENCH_admit.json / "
              "BENCH_step.json (not re-measured this run)", flush=True)
    try:
        with open("BENCH_admit.json") as f:
            rec = json.load(f)
    except FileNotFoundError:
        sys.exit("check: BENCH_admit.json not found — run "
                 "`python -m benchmarks.run admit` first")
    bad = [(b, s) for b, s in zip(rec["batch"], rec["speedup"])
           if b >= 256 and s < 1.3]
    if bad:
        sys.exit("check: admit regression gate FAILED — "
                 + ", ".join(f"speedup {s:.3f} < 1.3 at batch {b}"
                             for b, s in bad))
    print("# check: admit gate OK — "
          + ", ".join(f"{s:.2f}x@{b}" for b, s in
                      zip(rec["batch"], rec["speedup"]) if b >= 256),
          flush=True)
    try:
        with open("BENCH_step.json") as f:
            srec = json.load(f)
    except FileNotFoundError:
        sys.exit("check: BENCH_step.json not found — run "
                 "`python -m benchmarks.run step` first")
    floor = [(p, s) for p, s in zip(srec["pool"], srec["speedup"])
             if p == "2x16" and s < 0.8]
    if floor:
        sys.exit("check: completion-kernel floor FAILED — "
                 + ", ".join(f"fused/staged {s:.3f} < 0.8 at pool {p}"
                             for p, s in floor))
    print("# check: completion floor OK — "
          + ", ".join(f"{s:.2f}x@{p}" for p, s in
                      zip(srec["pool"], srec["speedup"]) if p == "2x16"),
          flush=True)
    smoke_engines()
    smoke_shards()
    smoke_policies()
    check_degraded()
    check_graded()
    check_chain()
    check_chaos()
    check_analysis()
    check_sanitize()


def check_analysis() -> None:
    """--check leg for the datapath verifier: ``python -m repro.analysis``
    (jaxpr safety pass over every registered kernel x fold, AST lints +
    import-graph containment, the plan-op sweep and the oracle/host
    lowering smoke) must come back with zero findings."""
    from repro.analysis.__main__ import main as analysis_main
    if analysis_main([]) != 0:
        sys.exit("check: analysis gate FAILED — datapath verifier findings "
                 "(report above)")
    print("# check: analysis gate OK — verifier/lint/plans/lowerings clean",
          flush=True)


def check_sanitize() -> None:
    """--check leg for the checkify sanitizer: the tier-1 suite runs once
    with XLB_SANITIZE=1, so every kernel-wrapper call discharges the
    conservation laws in-graph and every ServeLoop/ChainRunner tick asserts
    the host laws.  Overhead is roughly 1.3-1.5x suite wall time (checkify
    retrace + the per-tick host asserts) — documented in
    benchmarks/README.md; the sanitizer is strictly opt-in and never in the
    measured path."""
    import os
    import subprocess
    # the suite is the CPU suite (kernels in interpret mode): its process is
    # pinned to the CPU, so it never contends for a chip this one holds
    env = {**os.environ, "XLB_SANITIZE": "1", "JAX_PLATFORMS": "cpu"}
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in ("src", env.get("PYTHONPATH", "")) if p)
    t0 = time.perf_counter()
    proc = subprocess.run([sys.executable, "-m", "pytest", "-x", "-q"],
                          env=env)
    dt = time.perf_counter() - t0
    if proc.returncode != 0:
        sys.exit("check: sanitizer leg FAILED — tier-1 under XLB_SANITIZE=1 "
                 f"exited {proc.returncode}")
    print(f"# check: sanitizer leg OK — tier-1 clean under XLB_SANITIZE=1 "
          f"({dt:.0f}s)", flush=True)


# the launcher defaults to the paper's 50-instance fleet; the --check
# smokes pass their own CPU-sized one
_TINY_FLEET = ("--instances", "2", "--slots", "2", "--admit-batch", "8",
               "--max-len", "6")


def smoke_engines() -> None:
    """Protocol-drift gate: boot ``launch/serve.py`` in-process for every
    engine kind at a tiny config and require full completion.  Catches
    Balancer/ServeLoop contract breaks that per-module unit tests can't
    see (a wrong ``out`` key, a state type that stops round-tripping)."""
    from repro.core.balancer import ENGINE_KINDS
    from repro.launch import serve
    n_req = 4
    for kind in ENGINE_KINDS:
        done = serve.main(["--engine", kind, *_TINY_FLEET,
                           "--requests", str(n_req)])
        if done != n_req:
            sys.exit(f"check: engine smoke FAILED — {kind} completed "
                     f"{done}/{n_req} requests")
        print(f"# check: engine smoke OK — {kind} {done}/{n_req}",
              flush=True)


def _serve_leg(argv: list, n_req: int) -> None:
    """``launch/serve.py`` with ``argv``; every request must complete (a
    ``_sharded_leg`` when ``argv`` asks for ``--shards``)."""
    from repro.launch import serve
    done = serve.main(argv)
    if done != n_req:
        sys.exit(f"serve {' '.join(argv)} FAILED — completed "
                 f"{done}/{n_req} requests")


def smoke_shards(shards: int = 2) -> None:
    """--check gate for the scale-out layer: boot ``launch/serve.py
    --shards 2`` and require every request to complete through the
    sharded admission datapath."""
    n_req = 4
    _sharded_leg(shards, _serve_leg, ["--shards", str(shards), *_TINY_FLEET,
                                      "--requests", str(n_req)], n_req,
                 what="check: sharded serve smoke", timeout=1200)
    print(f"# check: sharded serve smoke OK — --shards {shards} "
          f"{n_req}/{n_req}", flush=True)


def smoke_policies(shards: int = 2) -> None:
    """--check gate for the policy-registry seam: serve to completion under
    the hash-keyed policies — maglev in-process on one host, affinity on a
    2-way sharded mesh (exercising the affinity-cache reconciliation
    collective end-to-end)."""
    n_req = 4
    _serve_leg(["--engine", "xlb", "--policy", "maglev", *_TINY_FLEET,
                "--requests", str(n_req)], n_req)
    print(f"# check: policy smoke OK — maglev {n_req}/{n_req}", flush=True)
    _sharded_leg(shards, _serve_leg,
                 ["--policy", "affinity", "--shards", str(shards),
                  *_TINY_FLEET, "--requests", str(n_req)], n_req,
                 what="check: affinity sharded serve smoke", timeout=1200)
    print(f"# check: policy smoke OK — affinity --shards {shards} "
          f"{n_req}/{n_req}", flush=True)


BENCHES = {
    "admit": bench_admit, "step": bench_step, "shard": bench_shard,
    "degraded": bench_degraded, "chain": bench_chain,
    "chaos": bench_chaos,
    "table1": bench_table1, "table2": bench_table2, "fig5": bench_fig5,
    "fig6": bench_fig6, "fig7": bench_fig7, "fig8": bench_fig8,
    "fig9": bench_fig9, "fig10": bench_fig10, "fig11": bench_fig11,
    "fig12": bench_fig12,
}


def main() -> None:
    global _POLICY
    from repro.launch.cache import setup_compile_cache
    setup_compile_cache()
    args = sys.argv[1:]
    if "--policy" in args:
        i = args.index("--policy")
        if i + 1 >= len(args):
            sys.exit("usage: --policy NAME (a name from "
                     "core/policy_defs.py::POLICY_NAMES)")
        from repro.core.routing_table import POLICY_NAMES
        if args[i + 1] not in POLICY_NAMES:
            sys.exit(f"unknown policy {args[i + 1]!r}; choose from: "
                     + ", ".join(sorted(POLICY_NAMES)))
        _POLICY = args[i + 1]
        args = args[:i] + args[i + 2:]
    json_out = None
    if "--json" in args:
        i = args.index("--json")
        if i + 1 >= len(args):
            sys.exit("usage: python -m benchmarks.run [BENCH ...] "
                     "--json OUT.json [--check]")
        json_out = args[i + 1]
        args = args[:i] + args[i + 2:]
    check = "--check" in args
    if check:
        args = [a for a in args if a != "--check"]
        if not args:                 # bare --check: gate the recorded file
            if json_out is not None:
                sys.exit("usage: --json needs explicit bench names when "
                         "combined with --check (bare --check only gates "
                         "the recorded BENCH_admit.json, running nothing)")
            check_gates()
            return
    names = args or list(BENCHES)
    unknown = [n for n in names if n not in BENCHES]
    if unknown:
        sys.exit(f"unknown bench {', '.join(unknown)}; "
                 f"choose from: {', '.join(BENCHES)}")
    print("bench,mode,metric,value")
    for n in names:
        BENCHES[n]()
    t1 = {m: v for b, m, k, v in ROWS if b == "table1" and k == "req_per_s"}
    if "xlb" in t1 and t1.get("istio"):
        print(f"# headline: xlb/istio throughput = "
              f"{t1['xlb'] / t1['istio']:.2f}x  (paper: >=1.5x)")
    if json_out:
        with open(json_out, "w") as f:
            json.dump([{"bench": b, "mode": m, "metric": k, "value": v}
                       for b, m, k, v in ROWS], f, indent=2)
            f.write("\n")
        print(f"# wrote {json_out}", flush=True)
    if check:
        check_gates(remeasured="admit" in names)


if __name__ == "__main__":
    main()
