"""Workload subsystem tests (src/repro/workload/ + the bench chain driver).

Pins the DESIGN.md §10 contracts: keyed-draw determinism of the arrival
processes and service-time laws, exact per-request delays through the
progress-rollback shaper, chain traversal (end-to-end latency = sum of
per-hop tick latencies), the live-ops scenario ops as single ControlPlane
transactions, elastic ``scale_fleet`` semantics, the out-of-window fault
regression, the scenario-row schema validator, and bit-identical replay of
BENCH_TREND scenario rows under a fixed seed."""

import json
import types

import numpy as np
import pytest

from repro.core.control import ControlPlane
from repro.core.routing_table import (Cluster, POLICY_RR, POLICY_WEIGHTED,
                                      Rule, ServiceConfig)
from repro.runtime.elastic import scale_fleet
from repro.runtime.serve_loop import Fault, FaultInjector
from repro.workload import (BurstyArrivals, DiurnalArrivals,
                            LognormalServiceTimes, Op, ParetoServiceTimes,
                            PoissonArrivals, ScenarioDriver,
                            ServiceTimeShaper, Workload, append_scenario_row,
                            percentiles, rolling_restart, scenario_row,
                            validate_scenario_row)


def _cp(n=3, policy=POLICY_WEIGHTED):
    return ControlPlane(
        [ServiceConfig("svc", rules=[Rule(0, None, "pool")])],
        [Cluster("pool", endpoints=list(range(n)), policy=policy)])


# --------------------------------------------------------------------------- #
# Generators
# --------------------------------------------------------------------------- #


def test_arrivals_keyed_determinism_and_seed_sensitivity():
    """Draws are keyed by (seed, tick): replays are bit-identical, the key
    is the *tick* (not call order), and a different seed is a different
    stream."""
    a = PoissonArrivals(rate=3.0, seed=1)
    trace = [a.arrivals(t) for t in range(64)]
    assert [a.arrivals(t) for t in range(64)] == trace
    # order-free: querying tick 7 in isolation matches the swept value
    assert a.arrivals(7) == trace[7]
    b = PoissonArrivals(rate=3.0, seed=2)
    assert [b.arrivals(t) for t in range(64)] != trace


def test_scale_knob_multiplies_offered_rate():
    base = PoissonArrivals(rate=2.0, seed=3)
    scaled = PoissonArrivals(rate=2.0, seed=3, scale=8.0)
    n_base = sum(base.arrivals(t) for t in range(200))
    n_scaled = sum(scaled.arrivals(t) for t in range(200))
    assert n_scaled > 4 * n_base          # ~8x in expectation


def test_bursty_and_diurnal_shapes():
    b = BurstyArrivals(rate=5.0, seed=0, on_ticks=4, off_ticks=4)
    assert all(b.arrivals(t) == 0 for t in range(4, 8))    # OFF is silent
    assert sum(b.arrivals(t) for t in range(0, 4)) > 0     # ON carries load
    d = DiurnalArrivals(rate=1.0, peak=9.0, period=64)
    assert d.rate_at(0) == pytest.approx(1.0)              # trough
    assert d.rate_at(32) == pytest.approx(9.0)             # peak
    assert d.rate_at(16) == pytest.approx(5.0)             # mid-swing


def test_service_time_laws_keying_and_bounds():
    ln = LognormalServiceTimes(seed=4, median=3.0, sigma=0.8, floor=1, cap=20)
    ts = [ln.ticks(r) for r in range(200)]
    assert ts == [ln.ticks(r) for r in range(200)]         # deterministic
    assert all(1 <= t <= 20 for t in ts)
    assert len(set(ts)) > 3                                # actually spread
    # the same request re-sampled at a different hop draws independently
    assert any(ln.ticks(r, hop=1) != ln.ticks(r, hop=0) for r in range(50))
    pa = ParetoServiceTimes(seed=4, xm=2.0, alpha=1.5, floor=1, cap=50)
    assert all(2 <= pa.ticks(r) <= 50 for r in range(200))


def test_shaper_enforces_exact_extra_ticks():
    """A request whose sampled time exceeds the base occupancy is held for
    exactly the difference — one effective rollback per extra tick."""
    law = LognormalServiceTimes(seed=9, median=6.0, sigma=0.5, cap=16)
    base = 2
    sh = ServiceTimeShaper(law, base_ticks=base, hop=0)
    rid = 5
    extra = max(0, law.ticks(rid, 0) - base)
    assert extra > 0                       # seed chosen to have a real hold
    pool = types.SimpleNamespace(
        req_id=np.array([[rid]], np.int32),
        active=np.array([[True]]),
        length=np.array([[1]], np.int32))
    holds = 0
    for t in range(extra + 5):
        before = pool.length.copy()
        sh.apply(pool, t)
        if pool.length[0, 0] != before[0, 0]:
            holds += 1
            pool.length[0, 0] = before[0, 0]   # engine re-makes the progress
    assert holds == extra
    # an idle slot (length 0) is never charged
    sh2 = ServiceTimeShaper(law, base_ticks=base)
    empty = types.SimpleNamespace(req_id=np.array([[rid]], np.int32),
                                  active=np.array([[True]]),
                                  length=np.array([[0]], np.int32))
    sh2.apply(empty, 0)
    assert empty.length[0, 0] == 0
    assert sh2._extra(rid) == extra        # nothing consumed


# --------------------------------------------------------------------------- #
# Scenario ops
# --------------------------------------------------------------------------- #


def test_canary_shifts_weights_in_one_txn():
    cp = _cp(3)
    drv = ScenarioDriver([cp], [Op(2, "canary", args={"instance": 0,
                                                      "pct": 80.0})])
    drv.apply(1)
    assert cp.version == 0                 # not due yet
    drv.apply(2)
    assert cp.version == 1 and drv.txns == 1     # ONE transaction
    assert cp.endpoint_weight("pool", 0) == pytest.approx(0.8)
    for peer in (1, 2):
        assert cp.endpoint_weight("pool", peer) == pytest.approx(0.1)
    assert drv.done()


def test_blue_green_cutover_single_txn():
    cp = _cp(2)
    ops = [Op(0, "add_endpoint", args={"instance": 2, "weight": 0.0}),
           Op(3, "blue_green", args={"blue": [0, 1], "green": [2]})]
    drv = ScenarioDriver([cp], ops)
    drv.apply(0)
    v = cp.version
    drv.apply(3)
    assert cp.version == v + 1             # cutover is one version bump
    assert cp.endpoint_weight("pool", 2) == pytest.approx(1.0)
    # with no in-flight load the drained blues are reaped at commit; green
    # alone serves either way
    serving = [i for _, i in cp.cluster_members("pool")
               if cp.drain_reason("pool", i) is None]
    assert serving == [2]


def test_rolling_restart_expansion_and_completion():
    cp = _cp(3)
    ops = rolling_restart([0, 1], start=2, dwell=3)
    assert [(o.tick, o.op) for o in ops] == [
        (2, "drain"), (5, "undrain"), (5, "drain"), (8, "undrain")]
    drv = ScenarioDriver([cp], ops)
    for t in range(9):
        drv.apply(t)
        draining = sum(1 for i in (0, 1)
                       if cp.drain_reason("pool", i) is not None)
        assert draining <= 1               # staggered: one down at a time
    assert drv.done() and drv.txns == 4
    for i in (0, 1):
        assert cp.drain_reason("pool", i) is None
        assert cp.endpoint_weight("pool", i) == pytest.approx(1.0)


def test_scale_fleet_up_down_one_txn_each():
    cp = _cp(2)
    v0 = cp.version
    acts = scale_fleet(cp, "pool", 4, max_instances=4)
    assert acts == [("add", 2), ("add", 3)]
    assert cp.version == v0 + 1
    assert sorted(i for _, i in cp.cluster_members("pool")) == [0, 1, 2, 3]
    acts = scale_fleet(cp, "pool", 1, max_instances=4)
    assert acts == [("drain", 1), ("drain", 2), ("drain", 3)]
    serving = [i for _, i in cp.cluster_members("pool")
               if cp.drain_reason("pool", i) is None]
    assert serving == [0]                  # highest-numbered drained first
    # zero-load drains were reaped at commit; scale-up re-adds fresh lanes
    scale_fleet(cp, "pool", 3, max_instances=4)
    serving = [i for _, i in cp.cluster_members("pool")
               if cp.drain_reason("pool", i) is None]
    assert len(serving) == 3
    with pytest.raises(ValueError):
        scale_fleet(cp, "pool", 9, max_instances=4)


def test_scale_fleet_undrains_loaded_endpoint_before_adding():
    """Scale-up prefers reviving a draining endpoint (kept alive by its
    in-flight load) over splicing in a fresh instance lane."""
    cp = _cp(2, policy=POLICY_RR)

    class _Holder:
        def __init__(self):
            self.routing = cp.snapshot()._replace(
                ep_load=np.ones_like(np.asarray(cp.snapshot().ep_load)))

        def apply_refresh(self, plan):
            pass                           # keep the pinned loads

    holder = _Holder()
    cp.attach(holder)                      # load votes pin drained rows
    acts = scale_fleet(cp, "pool", 1, max_instances=4)
    assert acts == [("drain", 1)]
    assert cp.drain_reason("pool", 1) is not None    # survived the reaper
    acts = scale_fleet(cp, "pool", 2, max_instances=4)
    assert acts == [("undrain", 1)]        # revived, no new lane spliced
    assert cp.drain_reason("pool", 1) is None


# --------------------------------------------------------------------------- #
# Fault-window regression (S3)
# --------------------------------------------------------------------------- #


def test_fault_outside_live_window_is_inert():
    """Regression: a flap fault naming an instance lane the pool doesn't
    have (schedule written for a bigger fleet, or racing an elastic scale
    on the same tick) used to IndexError on numpy pools / silently clip on
    jax pools.  It must be inert."""
    inj = FaultInjector([Fault(5, "flap", start=0, period=2),
                         Fault(-3, "stall", start=0)])
    pool = types.SimpleNamespace(
        req_id=np.array([[1, 2]], np.int32),
        active=np.array([[True, True]]),
        length=np.array([[2, 3]], np.int32))
    out = inj.apply(pool, 0)               # both faults hold at tick 0
    assert out is pool
    assert pool.length.tolist() == [[2, 3]]


def test_flap_fault_composes_with_elastic_scale():
    """The full composition the bug report names: flap fault + scale event
    live in the same run (one in-window target, one out-of-window) — the
    chain completes every request."""
    from benchmarks.common import run_chain_scenario
    inj = FaultInjector([Fault(1, "flap", start=0, end=6, period=1),
                         Fault(7, "flap", start=0, period=2)])
    out = run_chain_scenario(
        "istio", depth=1,
        workload=Workload(PoissonArrivals(rate=2.0, seed=5), n_requests=6),
        ops=[Op(1, "scale", args={"target": 1}),
             Op(4, "scale", args={"target": 2})],
        faults={0: inj})
    row = out["row"]
    assert row["completed"] == row["n_requests"] and row["dropped"] == 0
    assert row["txns"] == 2


# --------------------------------------------------------------------------- #
# Chain traversal
# --------------------------------------------------------------------------- #


def test_chain_end_to_end_is_sum_of_hops():
    """Forwarding is synchronous (hop k completion tick == hop k+1 submit
    tick), so end-to-end latency telescopes to the sum of per-hop
    latencies."""
    from benchmarks.common import run_chain_scenario
    res = run_chain_scenario(
        "istio", depth=3,
        workload=Workload(PoissonArrivals(rate=2.0, seed=11),
                          n_requests=10))["result"]
    assert res.completed == 10
    for r in res.done_tick:
        e2e = res.done_tick[r] - res.submit_tick[r]
        hops = sum(res.hop_done[k][r] - res.hop_submit[k][r]
                   for k in range(res.depth))
        assert e2e == hops
        for k in range(res.depth - 1):     # synchronous forwarding
            assert res.hop_submit[k + 1][r] == res.hop_done[k][r]


# --------------------------------------------------------------------------- #
# SLO rows
# --------------------------------------------------------------------------- #


def test_percentiles_empty_and_tail():
    p = percentiles([])
    assert p["n"] == 0 and np.isnan(p["p99"])
    p = percentiles(list(range(1, 101)))
    assert p["p50"] == pytest.approx(50.5)
    assert p["p99"] <= p["p999"] <= 100


def test_scenario_row_schema_validation():
    row = scenario_row("chain", "xlb", depth=3, seed=11, arrivals="poisson",
                       n_requests=10, completed=10, dropped=0, ticks=12,
                       samples=[3, 3, 4])
    validate_scenario_row(row)             # round-trips
    for bad, err in [
        (dict(row, bench="perf"), "bench"),
        (dict(row, completed=20), "exceeds"),
        (dict(row, p99_ticks=1.0), "monotone"),
        (dict(row, depth=True), "depth"),
        (dict(row, surprise=1), "unknown"),
    ]:
        with pytest.raises(ValueError, match=err):
            validate_scenario_row(bad)
    missing = dict(row)
    del missing["seed"]
    with pytest.raises(ValueError, match="missing"):
        validate_scenario_row(missing)
    with pytest.raises(ValueError, match="unknown"):
        scenario_row("chain", "xlb", depth=3, seed=11, arrivals="poisson",
                     n_requests=10, completed=10, dropped=0, ticks=12,
                     samples=[3], bogus_extra=1)


def test_append_scenario_row_stamps_and_appends(tmp_path):
    row = scenario_row("chain", "istio", depth=1, seed=0, arrivals="poisson",
                       n_requests=2, completed=2, dropped=0, ticks=3,
                       samples=[1, 2])
    path = tmp_path / "TREND.jsonl"
    stamped = append_scenario_row(row, path=str(path))
    assert "ts" in stamped and "commit" in stamped
    lines = path.read_text().strip().splitlines()
    assert len(lines) == 1
    back = json.loads(lines[0])
    validate_scenario_row(back)
    assert {k: back[k] for k in row} == row    # payload unchanged by stamp


# --------------------------------------------------------------------------- #
# Deterministic replay (S4)
# --------------------------------------------------------------------------- #


def _replay(workload_fn, **kw):
    from benchmarks.common import run_chain_scenario
    rows = [run_chain_scenario("istio", workload=workload_fn(), **kw)["row"]
            for _ in range(2)]
    assert rows[0] == rows[1]
    assert json.dumps(rows[0]) == json.dumps(rows[1])  # bit-identical JSONL
    return rows[0]


def test_replay_poisson_row_bit_identical():
    r = _replay(lambda: Workload(PoissonArrivals(rate=2.0, seed=11),
                                 n_requests=8), depth=3)
    assert r["completed"] == 8 and r["arrivals"] == "poisson"


def test_replay_bursty_row_bit_identical():
    r = _replay(lambda: Workload(
        BurstyArrivals(rate=4.0, seed=21, on_ticks=3, off_ticks=3),
        service=LognormalServiceTimes(seed=6, median=2.5, sigma=0.6, cap=10),
        n_requests=8), depth=2)
    assert r["arrivals"] == "bursty" and r["service"] == "lognormal"


def test_replay_depth3_chain_with_midrun_canary():
    r = _replay(lambda: Workload(PoissonArrivals(rate=2.0, seed=11),
                                 n_requests=8),
                depth=3, policy=POLICY_WEIGHTED,
                ops=[Op(3, "canary", hop=1,
                        args={"instance": 1, "pct": 75.0})])
    assert r["ops"] == 1 and r["txns"] == 1
    assert r["completed"] == 8
