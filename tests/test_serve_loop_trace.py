"""The serving loop's own trace: five phase spans a tick with their
counters (the one read a tick on ``loop.readback`` among them), the same
counts in ``tick()``'s return, and the named scopes of ``serve_step``.

Every test that starts the profiler lives in this one file, so that one
test worker holds it at a time."""

from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config, smoke_config
from repro.core import interpose
from repro.core.control import ControlPlane
from repro.core.routing_table import POLICY_RR, Cluster, Rule, ServiceConfig
from repro.models import model as M
from repro.runtime.serve_loop import Request, ServeLoop

PHASES = ("loop.control", "loop.admission", "loop.dispatch",
          "loop.readback", "loop.bookkeeping")
ADMIT_BATCH = 4


@pytest.fixture(scope="module")
def cfg_params():
    cfg = smoke_config(get_config("xlb-service-model"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def _loop(cfg_params) -> ServeLoop:
    """2 instances x 2 slots behind one path rule, fed 10 requests of which
    two match no rule: batches of 4 overflow the 4 slots, and the two
    unroutable requests are held until their second retry drops them."""
    cfg, params = cfg_params
    cp = ControlPlane([ServiceConfig("svc", rules=[Rule(0, "/a", "pool")])],
                      [Cluster("pool", endpoints=[0, 1], policy=POLICY_RR)])
    eng = interpose.Engine(cfg, 2, 2, max_len=3, eos=-1)   # length-driven
    loop = ServeLoop(eng, params, cp, admit_batch=ADMIT_BATCH, max_retries=2)
    for r in range(10):
        path = "/b" if r in (2, 7) else "/a"
        loop.submit(Request(req_id=r, service=0, headers={"path": path},
                            prompt_token=3 + r))
    return loop


def _drive(loop: ServeLoop, n: int) -> list[dict]:
    return [loop.tick() for _ in range(n)]


def _traced(loop: ServeLoop, n: int, trace_dir: Path):
    """``n`` ticks under the profiler: (their returns, the host events of
    the loop's spans in start order, as (name, start, end, metadata))."""
    jax.profiler.start_trace(str(trace_dir))
    try:
        outs = _drive(loop, n)
    finally:
        jax.profiler.stop_trace()
    path = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    events = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        {k: v for k, v in e.stats})
                       for e in line.events if e.name.startswith("loop.")]
    return outs, sorted(events, key=lambda e: e[1])


@pytest.fixture(scope="module")
def traced_run(cfg_params, tmp_path_factory):
    loop = _loop(cfg_params)
    _drive(loop, 1)                 # compile outside the traced ticks
    n = 8
    outs, events = _traced(loop, n, tmp_path_factory.mktemp("trace"))
    return n, outs, events


def test_each_tick_holds_the_five_phase_spans_in_order(traced_run):
    n, _, events = traced_run
    assert [e[0] for e in events] == list(PHASES) * n
    for a, b in zip(events, events[1:]):
        assert a[1] <= a[2] <= b[1]          # one after another, no overlap
    for name, _, _, meta in events:
        if name == "loop.admission":
            assert set(meta) == {"rows", "batch"}
            assert meta["batch"] == ADMIT_BATCH
            assert 0 <= meta["rows"] <= ADMIT_BATCH
        elif name == "loop.bookkeeping":
            assert set(meta) == {"held"}
        elif name == "loop.readback":
            assert set(meta) == {"reads", "bytes"}
        else:
            assert meta == {}


def test_span_counters_are_the_ticks_returns(traced_run):
    _, outs, events = traced_run
    meta = {p: [m for name, _, _, m in events if name == p]
            for p in ("loop.admission", "loop.bookkeeping")}
    assert [m["rows"] for m in meta["loop.admission"]] == [
        o["taken"] for o in outs]
    assert [m["held"] for m in meta["loop.bookkeeping"]] == [
        o["held"] for o in outs]
    assert sum(o["held"] for o in outs) > 0      # the run holds


def test_readback_is_one_read_of_the_packed_outputs(traced_run):
    """Each tick reads the step's outputs once: ``emitted``, ``done`` and
    ``req_id`` over the 2 x 2 pool and ``active``, as 13 int32 words."""
    n, _, events = traced_run
    meta = [m for name, _, _, m in events if name == "loop.readback"]
    assert meta == [{"reads": 1, "bytes": 4 * (3 * 2 * 2 + 1)}] * n


def test_counters_match_the_engines_flow_metrics(cfg_params):
    """Every attempt taken into a batch is admitted, overflows or matches no
    rule (disjoint in the kernel: ``held = routable & ~ok``); the attempts
    the loop counts as held are the engine's overflow and no-route
    events."""
    loop = _loop(cfg_params)
    outs = _drive(loop, 12)
    rows = sum(o["taken"] for o in outs)
    held = sum(o["held"] for o in outs)
    m = loop.state.metrics
    overflow, no_route = int(m.overflow), int(m.no_route_match)
    assert overflow > 0 and no_route > 0
    assert held == overflow + no_route
    assert rows == int(m.requests.sum()) + overflow + no_route
    assert len(loop.dropped) == 2                 # the unroutable pair


def test_untraced_ticks_return_the_same_counts(cfg_params, traced_run):
    n, traced, _ = traced_run
    loop = _loop(cfg_params)
    outs = _drive(loop, n + 1)[1:]    # the traced run's first tick was
    assert outs == traced             # untraced too: taken, held and the rest


def test_serve_step_carries_the_named_scopes(cfg_params):
    loop = _loop(cfg_params)
    reqs, _ = loop._next_admission()
    text = loop.serve_step.lower(loop.params, loop.state,
                                 reqs).compile().as_text()
    names = [ln.split('op_name="', 1)[1].split('"', 1)[0]
             for ln in text.splitlines() if 'op_name="' in ln]
    scoped = {s: [n for n in names if f"/{s}/" in n]
              for s in ("xlb_admit", "xlb_decode")}
    assert scoped["xlb_admit"] and scoped["xlb_decode"]
    # the gate itself sits in the admission scope, not only its branches
    assert "jit(serve_step)/xlb_admit/cond" in names
    assert not set(scoped["xlb_admit"]) & set(scoped["xlb_decode"])
