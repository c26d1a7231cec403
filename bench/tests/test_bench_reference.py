"""The plain reference agrees with the program's own oracles and decode on
the CPU (the reference imports nothing of the program; these tests do)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import check, deploy, reference
from bench.tests import tiny


def _routing(config):
    from repro.core.control import ControlPlane
    from repro.core.routing_table import POLICY_NAMES, Cluster, Rule, \
        ServiceConfig
    f = reference.HEADER_FIELDS
    cp = ControlPlane(
        [ServiceConfig(s["name"], [Rule(f.index(r["header"]), r["value"],
                                        r["cluster"]) for r in s["rules"]])
         for s in config["services"]],
        [Cluster(c["name"], *reference.cluster_endpoints(c)[:1],
                 policy=POLICY_NAMES[c["policy"]],
                 weights=reference.cluster_endpoints(c)[1])
         for c in config["clusters"]])
    return cp.snapshot()


def test_tables_match_the_control_plane_build():
    st = _routing(tiny.CONFIG)
    t = reference.RefTables.build(tiny.CONFIG, st.maglev_table.shape[1])
    view = {k: np.asarray(v) for k, v in st._asdict().items()}
    assert reference.table_mismatches(t, view) == 0
    view["rule_value"] = view["rule_value"].copy()
    view["rule_value"][2] += 1
    view["maglev_table"] = view["maglev_table"].copy()
    view["maglev_table"][4, 7] = (view["maglev_table"][4, 7] + 1) % 2
    assert reference.table_mismatches(t, view) == 2


def _case(seed, R=24):
    """A routing state with live counters, a half-full pool and a batch of
    every service, repeated flows, padding and an unmatched path."""
    st = _routing(tiny.CONFIG)
    g = np.random.default_rng(seed)
    E, CL = st.ep_load.shape[0], st.rr_cursor.shape[0]
    n_ep = int(np.asarray(st.cluster_ep_count).sum())
    load = np.zeros(E, np.int32)
    load[:n_ep] = g.integers(0, 3, n_ep)
    cur = np.zeros(CL, np.int32)
    cur[:7] = g.integers(0, 5, 7)
    st = st._replace(ep_load=jnp.asarray(load), rr_cursor=jnp.asarray(cur))
    I, C = tiny.CONFIG["instances"], tiny.CONFIG["slots"]
    act = g.random((I, C)) < 0.4
    pool = {"pool_req_id": np.where(act, 900 + np.arange(I * C).reshape(I, C),
                                    -1),
            "pool_endpoint": np.where(act, g.integers(0, n_ep, (I, C)), -1),
            "pool_svc": g.integers(0, 6, (I, C)),
            "pool_length": np.where(act, g.integers(0, 4, (I, C)), 0),
            "pool_token": g.integers(2, 500, (I, C)),
            "pool_active": act.astype(np.int64)}
    users = ["jason", "u1", "u2", "u3"]
    svc = g.integers(0, 6, R).astype(np.int32)
    svc[:3] = 0                          # service a: the first unmatched
    hdr = [{"path": "/zz" if s == 0 and r % 3 == 0 else "/a",
            "user": users[r % 4]} for r, s in enumerate(svc)]
    rid = np.where(g.random(R) < 0.85, np.arange(R), -1).astype(np.int32)
    rid[0] = 0
    batch = {"req_id": rid, "svc": svc,
             "features": np.stack([reference.features(h) for h in hdr]),
             "token": g.integers(2, 500, R).astype(np.int32),
             "msg_bytes": g.integers(1, 900, R).astype(np.int32)}
    return st, pool, batch


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_admission_matches_the_program_oracle(seed):
    from repro.kernels import ref
    st, pool, batch = _case(seed)
    key = jax.random.PRNGKey(seed + 100)
    state = {**{k: np.asarray(v) for k, v in st._asdict().items()}, **pool,
             "key": np.asarray(key), "requests": np.zeros(64, np.int64),
             "tx_bytes": np.zeros(64, np.int64), "no_route_match": 0,
             "overflow": 0}
    t = reference.RefTables.build(tiny.CONFIG, st.maglev_table.shape[1])
    got = reference.admit(t, state, batch)
    _, rnd, gum = reference.draws(key, batch["req_id"].shape[0])
    want = ref.admit_commit_ref(
        batch["req_id"], batch["svc"], batch["features"],
        batch["msg_bytes"], batch["token"], st,
        *(pool[f"pool_{k}"] for k in check.POOL_FIELDS),
        jnp.asarray(rnd, jnp.int32), gum)
    for k in check.POOL_FIELDS:
        np.testing.assert_array_equal(got[f"pool_{k}"],
                                      getattr(want, f"pool_{k}"))
    for k in ("ep_load", "rr_cursor", "aff_key", "aff_ep"):
        np.testing.assert_array_equal(got[k], getattr(want, k))
    np.testing.assert_array_equal(got["requests"], want.svc_requests)
    np.testing.assert_array_equal(got["tx_bytes"], want.svc_tx_bytes)
    assert got["no_route_match"] == int(want.no_route) > 0
    assert got["overflow"] == int(want.held)


@pytest.mark.parametrize("seed", [0, 1])
def test_completion_matches_the_program_oracle(seed):
    from repro.kernels import ref
    st, pool, _ = _case(seed)
    g = np.random.default_rng(seed)
    nxt = np.where(g.random(pool["pool_active"].shape) < 0.3, 1,
                   g.integers(2, 500, pool["pool_active"].shape))
    ewl = g.uniform(0, 4, st.ep_load.shape[0]).astype(np.float32)
    ewt = g.uniform(0, 2, st.ep_load.shape[0]).astype(np.float32)
    rx = g.integers(0, 100, 64)
    state = {**pool, "ep_load": np.asarray(st.ep_load), "rx_bytes": rx,
             "ep_inflight_ewma": ewl, "ep_tput_ewma": ewt}
    got = reference.complete(state, nxt, eos=1, max_len=5)
    want = ref.complete_ref(*(pool[f"pool_{k}"] for k in check.POOL_FIELDS),
                            nxt, st.ep_load, rx, ewl, ewt, eos=1, max_len=5)
    for k in check.POOL_FIELDS:
        np.testing.assert_array_equal(got[f"pool_{k}"], getattr(want, k))
    for k, w in (("done", want.done), ("ep_load", want.ep_load),
                 ("rx_bytes", want.rx_bytes),
                 ("ep_inflight_ewma", want.inflight_ewma),
                 ("ep_tput_ewma", want.tput_ewma)):
        np.testing.assert_array_equal(got[k], w)


def test_forward_matches_the_program_decode():
    """The full-sequence reference against the program's decode step fed
    token by token through its cache (float32 on the CPU)."""
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config(tiny.APP["name"])
    params = deploy.make_params(deploy.param_shapes(cfg, jnp.float32), 3)
    toks = np.random.default_rng(0).integers(2, 512, (3, 6)).astype(np.int32)
    cache = M.init_cache(cfg, 3, 8, jnp.float32)
    steps = []
    for p in range(6):
        lg, cache = M.decode_step(cfg, params, jnp.asarray(toks[:, p:p + 1]),
                                  jnp.full((3,), p, jnp.int32), cache)
        steps.append(np.asarray(lg))
    want = np.stack(steps, 1)
    got = np.asarray(reference.forward_logits(tiny.APP, params,
                                              jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
    gaps = reference.served_gaps(got[:, :-1], want[:, :-1].argmax(-1))
    assert gaps.max() < 1e-4


def test_ewma_rounds_as_the_backend_does(monkeypatch):
    """An idle endpoint's EWMA decays by 3/4 a step; on a TPU, whose
    float32 results flush to zero below the smallest normal number, it
    stops where a quarter of it would be subnormal."""
    prev = np.float32([4.339851612060788e-38, 1.0])
    obs = np.float32([0.0, 0.0])
    np.testing.assert_array_equal(
        reference.ewma(prev, obs, reference.ALPHA_INFLIGHT),
        np.float32([0.75 * 4.339851612060788e-38, 0.75]))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    np.testing.assert_array_equal(
        reference.ewma(prev, obs, reference.ALPHA_INFLIGHT),
        np.float32([4.339851612060788e-38, 0.75]))
