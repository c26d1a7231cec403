"""Host serving-driver tests: drop accounting, ragged and empty admission
batches end-to-end through ServeLoop.tick (the lax.cond skip path),
pool/metrics invariants across a full drain, the drain report, and the
control-plane seam (zero-recompilation refresh, three-engine visibility)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core import interpose
from repro.core.balancer import ENGINE_KINDS, make_balancer
from repro.core.control import ControlPlane
from repro.core.routing_table import (Cluster, POLICY_LEAST_REQUEST,
                                      POLICY_RANDOM, POLICY_RR, Rule,
                                      ServiceConfig, build_state)
from repro.models import model as M
from repro.runtime.serve_loop import Request, ServeLoop

I, C = 2, 3


@pytest.fixture(scope="module")
def setup():
    cfg = smoke_config(get_config("xlb-service-model"))
    params = M.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    return cfg, params


def _routing(require_match: bool):
    """require_match=True: the only rule matches field0 == hash('v2'), so a
    request without that header is NO_ROUTE forever."""
    services = [ServiceConfig("svc", rules=[
        Rule(0, "v2" if require_match else None, "pool")])]
    clusters = [Cluster("pool", endpoints=list(range(I)), policy=POLICY_RR)]
    routing, _ = build_state(services, clusters)
    return routing


def _loop(cfg, params, *, require_match=False, admit_batch=8, max_len=5,
          **kw):
    eng = interpose.Engine(cfg, I, C, max_len)
    return ServeLoop(eng, params, _routing(require_match),
                     admit_batch=admit_batch, **kw)


def _req(rid, headers=None):
    return Request(req_id=rid, service=0, headers=headers or {},
                   prompt_token=3 + rid % 7)


def test_drain_accounts_for_dropped_requests(setup):
    """Requests that exhaust ``max_retries`` land on ``dropped`` — after a
    drain, submitted == done + dropped + queued + inflight, where queued
    includes the backoff waiting set."""
    cfg, params = setup
    loop = _loop(cfg, params, require_match=True,
                 max_retries=6, backoff_cap=4)
    routable = [_req(r, {"path": "v2"}) for r in range(3)]
    unroutable = [_req(100 + r) for r in range(2)]     # no matching header
    for r in routable + unroutable:
        loop.submit(r)
    loop.drain(max_ticks=200)
    n_sub = len(routable) + len(unroutable)
    assert n_sub == (len(loop.done) + len(loop.dropped) + loop.n_queued
                     + len(loop.inflight))
    assert {r.req_id for r in loop.done} == {0, 1, 2}
    assert {r.req_id for r in loop.dropped} == {100, 101}
    assert all(r.retries == loop.max_retries for r in loop.dropped)
    assert all(r.t_done > 0 for r in loop.dropped)     # latency accounting
    assert int(np.asarray(loop.state.metrics.no_route_match)) > 0


def test_ragged_admission_batch_invariants(setup):
    """admit_batch larger than the queue: padding rows must admit nothing,
    touch no counters, and the real rows must all land in pool slots."""
    cfg, params = setup
    loop = _loop(cfg, params, admit_batch=8)
    for r in range(3):                                 # 3 real + 5 padding
        loop.submit(_req(r))
    loop.tick()
    st = loop.state
    assert int(np.asarray(st.pool.active).sum()) == 3
    assert int(np.asarray(st.metrics.requests).sum()) == 3
    assert int(np.asarray(st.metrics.no_route_match)) == 0
    assert int(np.asarray(st.metrics.overflow)) == 0
    # load counters track exactly the live connections
    assert int(np.asarray(st.routing.ep_load).sum()) == 3
    pool_ids = set(np.asarray(st.pool.req_id)[np.asarray(st.pool.active)])
    assert pool_ids == {0, 1, 2}


def test_empty_admission_batch_skips_admit(setup):
    """An all-padding batch takes make_jitted's lax.cond skip path: decode
    continues, admission state (metrics, cursors, key-driven counters) is
    untouched."""
    cfg, params = setup
    loop = _loop(cfg, params, max_len=16)              # no completions yet
    for r in range(2):
        loop.submit(_req(r))
    loop.tick()
    st1 = loop.state
    req1 = int(np.asarray(st1.metrics.requests).sum())
    cur1 = np.asarray(st1.routing.rr_cursor).copy()
    loop.tick()                                        # queue empty now
    st2 = loop.state
    assert int(np.asarray(st2.metrics.requests).sum()) == req1 == 2
    np.testing.assert_array_equal(np.asarray(st2.routing.rr_cursor), cur1)
    assert int(np.asarray(st2.pool.active).sum()) == 2
    # decode still advanced every active lane
    act = np.asarray(st2.pool.active)
    assert (np.asarray(st2.pool.length)[act]
            > np.asarray(st1.pool.length)[act]).all()


def test_drain_releases_all_load(setup):
    """After a clean drain every connection closed: pools empty, endpoint
    load counters fully released, rx bytes strictly positive."""
    cfg, params = setup
    loop = _loop(cfg, params, max_len=4)
    for r in range(5):                                 # 5 reqs through 6 slots
        loop.submit(_req(r))
    rep = loop.drain(max_ticks=100)
    assert len(rep.done) == 5 and not rep.dropped
    assert rep.queued == 0 and rep.inflight == 0
    st = loop.state
    assert int(np.asarray(st.pool.active).sum()) == 0
    np.testing.assert_array_equal(np.asarray(st.routing.ep_load),
                                  np.zeros_like(np.asarray(
                                      st.routing.ep_load)))
    assert int(np.asarray(st.metrics.rx_bytes).sum()) > 0


def _cp_pool(policy=POLICY_RR):
    return ControlPlane(
        [ServiceConfig("svc", rules=[Rule(0, None, "pool")])],
        [Cluster("pool", endpoints=list(range(I)), policy=policy)])


def test_drain_reports_stranded_work(setup):
    """drain() must say what it left behind (queued/inflight), not just
    return the completions."""
    cfg, params = setup
    loop = _loop(cfg, params, require_match=True)
    for r in range(2):
        loop.submit(_req(r, {"path": "v2"}))
    loop.submit(_req(50))                      # unroutable: no v2 header
    rep = loop.drain(max_ticks=30)    # far from max_retries: still queued
    assert {r.req_id for r in rep.done} == {0, 1}
    assert rep.queued == 1 and rep.inflight == 0
    assert rep.queued == loop.n_queued           # ready queue + backoff set
    assert not rep.dropped


def test_delta_refresh_zero_recompilation(setup):
    """The paper's no-disturbance property, pinned: a ControlPlane
    transaction between ticks (endpoint add → window relocation + a weight
    change) must not add a single entry to the jitted serve_step cache —
    the datapath re-reads new buffers, it is never re-traced."""
    cfg, params = setup
    cp = _cp_pool()
    eng = interpose.Engine(cfg, I, C, 16)
    loop = ServeLoop(eng, params, cp, admit_batch=4)
    for r in range(2):
        loop.submit(_req(r))
    loop.tick()
    loop.tick()                                # both cond branches traced
    n0 = loop.serve_step._cache_size()
    assert n0 >= 1
    with cp.transaction():                     # relocates the full window
        cp.add_endpoint("pool", instance=1)
        cp.set_weight("pool", instance=0, weight=2.0)
    loop.submit(_req(7))
    loop.tick()
    loop.tick()
    assert loop.serve_step._cache_size() == n0
    assert int(np.asarray(loop.routing.version)) == 1
    assert int(np.asarray(
        loop.routing.cluster_ep_count)[cp.cluster_id("pool")]) == I + 1


@pytest.mark.parametrize("policy", [POLICY_RR, POLICY_RANDOM,
                                    POLICY_LEAST_REQUEST])
def test_drain_endpoint_stops_new_traffic_mid_serve(setup, policy):
    """The ROADMAP gap, closed: ``drain_endpoint`` on a LOADED endpoint
    must stop new admissions under rr/random/least-request (not just
    WEIGHTED) via the datapath-visible ``ep_drained`` mask, while the
    in-flight connection keeps its slot until it completes."""
    cfg, params = setup
    cp = _cp_pool(POLICY_RR)      # rr places the pre-drain pair one per
    eng = interpose.Engine(cfg, I, C, max_len=32)      # instance; nothing
    loop = ServeLoop(eng, params, cp, admit_batch=2)   # completes
    for r in range(2):
        loop.submit(_req(r))
    loop.tick()
    slot = cp.endpoint_slot("pool", 1)
    assert int(np.asarray(loop.routing.ep_load)[slot]) == 1
    cp.set_policy("pool", policy)                      # the policy under test
    cp.drain_endpoint("pool", 1)                       # loaded → masked,
    assert cp.endpoint_slot("pool", 1) == slot         # not reaped
    assert int(np.asarray(loop.routing.ep_drained)[slot]) == 1
    for r in range(10, 14):
        loop.submit(_req(r))
    loop.tick()
    loop.tick()
    pool = loop.state.pool
    act = np.asarray(pool.active)
    pe = np.asarray(pool.endpoint)
    # every NEW admission avoided the draining endpoint: it still holds
    # exactly its one pre-drain connection, instance 0 absorbed the rest
    assert int(((pe == slot) & act).sum()) == 1
    assert int(np.asarray(loop.routing.ep_load)[slot]) == 1
    assert int(act.sum()) > 2                          # traffic kept flowing


def test_weight_update_visible_to_all_three_engines(setup):
    """One ControlPlane, three attached engines: a committed weight change
    reaches the XLB device tables and both sidecar host routers alike."""
    cfg, params = setup
    cp = _cp_pool()
    loops = {k: ServeLoop(make_balancer(k, cfg, I, C, 5), params, cp)
             for k in ENGINE_KINDS}
    with cp.transaction():
        cp.set_weight("pool", instance=1, weight=7.5)
    slot = cp.endpoint_slot("pool", 1)
    for kind, lp in loops.items():
        assert float(np.asarray(lp.routing.ep_weight)[slot]) == 7.5, kind
        assert int(np.asarray(lp.routing.version)) == 1, kind


def test_held_request_overflow_is_bounded_and_documented(setup):
    """Regression: ``Engine.admit`` adds ``res.held`` into
    ``metrics.overflow`` on EVERY attempt, so one request re-queued k times
    used to read like k distinct pool exhaustions.  The semantics are now
    pinned (FlowMetrics docstring): ``overflow`` counts hold events per
    attempt — exactly the held request's retry count, bounded by the host's
    64-retry cap — while ``ServeLoop.held_first`` counts the REQUEST once,
    however long it waited."""
    cfg, params = setup
    eng = interpose.Engine(cfg, 1, 1, max_len=5)       # one slot total
    services = [ServiceConfig("svc", rules=[Rule(0, None, "pool")])]
    clusters = [Cluster("pool", endpoints=[0], policy=POLICY_RR)]
    routing, _ = build_state(services, clusters)
    loop = ServeLoop(eng, params, routing, admit_batch=2)
    loop.submit(_req(0))
    loop.submit(_req(1))           # held until request 0 frees the slot
    rep = loop.drain(max_ticks=100)
    assert {r.req_id for r in rep.done} == {0, 1}
    held = next(r for r in rep.done if r.req_id == 1)
    assert held.retries >= 1                    # it really was held
    overflow = int(np.asarray(loop.state.metrics.overflow))
    # one hold event per failed attempt, nothing more: the eventually-
    # admitted request contributes exactly its retry count (< 64), not 64x
    assert overflow == held.retries
    assert loop.held_first == 1 == rep.held_first
    assert rep.held_first < loop.max_retries


def test_retry_backoff_is_capped_exponential_and_deterministic(setup):
    """Satellite regression: held requests back off exponentially (capped)
    with deterministic seeded jitter instead of hammering the admit path
    every tick — and the accounting identity holds at every tick, with the
    backoff waiting set counted as queued."""
    cfg, params = setup

    def run(seed):
        loop = _loop(cfg, params, require_match=True,
                     max_retries=5, backoff_cap=4, backoff_seed=seed)
        loop.submit(_req(0, {"path": "v2"}))
        loop.submit(_req(9))                   # unroutable: retries forever
        drop_tick, attempts = None, []
        for t in range(64):
            loop.tick()
            # the identity holds mid-flight, not just after a drain
            assert 2 == (len(loop.done) + len(loop.dropped)
                         + loop.n_queued + len(loop.inflight)), t
            if loop.dropped and drop_tick is None:
                drop_tick = t
            attempts.append(loop.dropped[0].retries if loop.dropped
                            else None)
        return loop, drop_tick, attempts

    loop_a, drop_a, sched_a = run(seed=3)
    loop_b, drop_b, sched_b = run(seed=3)
    assert drop_a is not None                  # it did give up eventually
    assert (drop_a, sched_a) == (drop_b, sched_b)   # bit-identical replay
    # exponential spacing really happened: 5 attempts with delays
    # ≥ 1,1,2,4 (cap 4) + jitter can't finish in the first 7 ticks
    assert drop_a > 7
    assert loop_a.dropped[0].retries == loop_a.max_retries
    # the routable request was never starved by the backoff machinery
    assert {r.req_id for r in loop_a.done} == {0}


def test_heartbeat_sent_each_tick_when_attached(setup):
    """A ServeLoop driven from a ControlPlane heartbeats its liveness lease
    every tick, so the drain reaper keeps honoring its load votes."""
    cfg, params = setup
    cp_lease = ControlPlane(
        [ServiceConfig("svc", rules=[Rule(0, None, "pool")])],
        [Cluster("pool", endpoints=list(range(I)), policy=POLICY_RR)],
        lease_epochs=1)
    eng = interpose.Engine(cfg, I, C, 5)
    loop = ServeLoop(eng, params, cp_lease, admit_batch=4)
    for _ in range(3):
        cp_lease.advance_epoch()
        loop.tick()
    assert cp_lease._lease_live(loop)          # fresh at every epoch
    for _ in range(3):                         # stop ticking: lease expires
        cp_lease.advance_epoch()
    assert not cp_lease._lease_live(loop)
