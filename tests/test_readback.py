"""The step's packed ``wire`` output and ``ServeLoop``'s one read a tick.

Every engine's ``out["wire"]`` is ``emitted``, ``done`` and ``req_id``
raveled over the (I, C) pool, then ``active``, in one int32 vector; the
loop reads only that vector.  A run through it serves the same tokens, in
the same done order, with the same holds and drops, as a reference that
reads the four keys themselves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.core.balancer import ENGINE_KINDS, make_balancer
from repro.core.control import ControlPlane
from repro.core.routing_table import POLICY_RR, Cluster, Rule, ServiceConfig
from repro.models import model as M
from repro.runtime.serve_loop import Request, ServeLoop

I, C = 2, 2
MAX_RETRIES = 2
N_REQ = 12
UNROUTABLE = (2, 7)


@pytest.fixture(scope="module")
def cfg_params():
    cfg = smoke_config(get_config("xlb-service-model"))
    return cfg, M.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)


class Tap:
    """Wraps a loop's ``serve_step`` and keeps, per tick, the batch's ids
    and the four outputs read one by one, beside the packed vector."""

    def __init__(self, step):
        self.step = step
        self.ticks: list = []

    def __call__(self, params, state, reqs):
        state, out = self.step(params, state, reqs)
        self.ticks.append(dict(
            taken=[int(r) for r in np.asarray(reqs.req_id) if r >= 0],
            emitted=np.asarray(out["emitted"]),
            done=np.asarray(out["done"]),
            req_id=np.asarray(out["req_id"]),
            active=int(out["active"]),
            wire=np.asarray(out["wire"])))
        return state, out


def _loop(kind: str, cfg_params) -> tuple[ServeLoop, Tap]:
    """2 instances x 2 slots behind one path rule, fed seeded prompts of
    which two match no rule: batches of 4 overflow the 4 slots, so the
    run admits, completes (length-driven), holds and drops."""
    cfg, params = cfg_params
    cp = ControlPlane([ServiceConfig("svc", rules=[Rule(0, "/a", "pool")])],
                      [Cluster("pool", endpoints=[0, 1], policy=POLICY_RR)])
    eng = make_balancer(kind, cfg, I, C, max_len=4, eos=-1)
    loop = ServeLoop(eng, params, cp, admit_batch=4,
                     max_retries=MAX_RETRIES, backoff_seed=11)
    tokens = np.random.default_rng(5).integers(2, cfg.vocab, N_REQ)
    for r in range(N_REQ):
        path = "/b" if r in UNROUTABLE else "/a"
        loop.submit(Request(req_id=r, service=0, headers={"path": path},
                            prompt_token=int(tokens[r])))
    tap = Tap(loop.serve_step)
    loop.serve_step = tap
    return loop, tap


@pytest.fixture(scope="module", params=ENGINE_KINDS)
def run(request, cfg_params):
    loop, tap = _loop(request.param, cfg_params)
    returns = [loop.tick() for _ in range(14)]
    assert not (loop.queue or loop._waiting or loop.inflight)
    return loop, tap, returns


def test_wire_unpacks_to_the_four_outputs(run):
    _, tap, returns = run
    n = I * C
    for t in tap.ticks:
        w = t["wire"]
        assert w.dtype == np.int32 and w.shape == (3 * n + 1,)
        np.testing.assert_array_equal(w[:n].reshape(I, C), t["emitted"])
        np.testing.assert_array_equal(w[n:2 * n].reshape(I, C),
                                      t["done"].astype(np.int32))
        np.testing.assert_array_equal(w[2 * n:3 * n].reshape(I, C),
                                      t["req_id"])
        assert w[-1] == t["active"]
    assert [r["active"] for r in returns] == [t["active"] for t in tap.ticks]
    # the run exercised admission, completion and holds
    assert any(t["taken"] for t in tap.ticks)
    assert any(t["done"].any() for t in tap.ticks)
    assert sum(r["held"] for r in returns) > 0


def _reference(ticks: list) -> dict:
    """Tokens, done order, holds and drops from the four keys alone: a
    serviced id (``req_id`` >= 0) gets its ``emitted`` token, row-major,
    and leaves on ``done``; an id taken into the batch that was not
    serviced is held, and dropped at its ``MAX_RETRIES``-th hold."""
    tokens: dict = {}
    order, held, holds = [], [], {}
    for t in ticks:
        ids = t["req_id"]
        serviced = set()
        for i in range(I):
            for s in range(C):
                rid = int(ids[i, s])
                if rid < 0:
                    continue
                serviced.add(rid)
                tokens.setdefault(rid, []).append(int(t["emitted"][i, s]))
                if t["done"][i, s]:
                    order.append(rid)
        h = [r for r in t["taken"] if r not in serviced]
        held.append(len(h))
        for r in h:
            holds[r] = holds.get(r, 0) + 1
    dropped = sorted(r for r, k in holds.items() if k >= MAX_RETRIES)
    return dict(tokens=tokens, order=order, held=held, dropped=dropped)


def test_loop_serves_what_the_four_outputs_say(run):
    loop, tap, returns = run
    ref = _reference(tap.ticks)
    assert [r.req_id for r in loop.done] == ref["order"]
    assert {r.req_id: r.tokens for r in loop.done} == {
        r: ref["tokens"][r] for r in ref["order"]}
    assert [r["held"] for r in returns] == ref["held"]
    dropped = sorted(r.req_id for r in loop.dropped)
    assert dropped == ref["dropped"]
    assert set(UNROUTABLE) <= set(dropped)
    assert len(loop.done) + len(dropped) == N_REQ
