"""Mesh-sharded admission tests (DESIGN.md §7).

In-process: the M=1 degenerate mesh is bit-exact vs the single-shard fused
kernel, the water-fill offset closed form matches a sequential argmin loop,
the shard-major oracle delegation, engine validation, and the control-plane
plan wire format.

Subprocess (4 virtual host devices, cf. tests/test_distributed.py): the
property sweep the reconciliation pass must survive — M ∈ {2, 4} against
single-shard ``admit_commit`` on the concatenated batch with uneven
per-shard queues, an all-padding shard (the per-shard lax.cond skip path),
drained endpoints visible to every shard, ragged batches, near-full pools
(global held resolution) — plus the 4-shard ``sharded_apply`` round-trip
vs the dense einsum oracle, and a mid-serve ControlPlane transaction
reaching every attached sharded consumer with exactly one version bump.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_shard_mesh
from repro.core import control
from repro.core.balancer import PoolState, RequestBatch
from repro.core.routing_table import (MAX_ENDPOINTS, MAX_EPS_PER_CLUSTER,
                                      MAX_SERVICES, N_FEATURES, Cluster,
                                      POLICY_AFFINITY, POLICY_LEAST_REQUEST,
                                      POLICY_MAGLEV, POLICY_RANDOM,
                                      POLICY_RR, POLICY_WEIGHTED, Rule,
                                      ServiceConfig, build_state, fnv1a)
from repro.kernels import ops, ref
from repro.kernels.shard_admit import waterfill_lr


def _rich_state():
    """All six policies + a no-rule service + preloaded counters + a drain
    on an endpoint shared by three clusters + a drained maglev window slot
    whose table row was NOT rebuilt (the defensive fallback path)."""
    svcs = [ServiceConfig("a", rules=[Rule(0, "x", "rr"), Rule(1, "y", "lr"),
                                      Rule(0, None, "wt")]),
            ServiceConfig("b", rules=[Rule(2, "z", "rnd"),
                                      Rule(3, "m", "mg"),
                                      Rule(1, None, "af")])]
    cls = [Cluster("rr", endpoints=[0, 1, 2], policy=POLICY_RR),
           Cluster("lr", endpoints=[1, 2, 3], policy=POLICY_LEAST_REQUEST),
           Cluster("wt", endpoints=[0, 3], policy=POLICY_WEIGHTED,
                   weights=[0.2, 5.0]),
           Cluster("rnd", endpoints=[2, 0], policy=POLICY_RANDOM),
           Cluster("mg", endpoints=[0, 1, 2, 3], policy=POLICY_MAGLEV),
           Cluster("af", endpoints=[3, 1, 2], policy=POLICY_AFFINITY)]
    st, _ = build_state(svcs, cls)
    return st._replace(
        ep_load=st.ep_load.at[:8].set(
            jnp.asarray([3, 0, 2, 1, 0, 0, 0, 0], jnp.int32)),
        rr_cursor=st.rr_cursor.at[0].set(2),
        ep_drained=st.ep_drained.at[1].set(1).at[11].set(1))


def _batch(R, seed, pad_slice=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    rid = jnp.where(jax.random.bernoulli(ks[0], 0.85, (R,)),
                    jnp.arange(R, dtype=jnp.int32), -1)
    if pad_slice is not None:
        rid = rid.at[pad_slice].set(-1)
    svc = jax.random.randint(ks[1], (R,), 0, 3, dtype=jnp.int32)
    feats = jnp.zeros((R, N_FEATURES), jnp.int32)
    feats = feats.at[:, 0].set(jnp.where(
        jax.random.bernoulli(ks[2], .5, (R,)), fnv1a("x"), 0))
    feats = feats.at[:, 1].set(jnp.where(
        jax.random.bernoulli(ks[3], .5, (R,)), fnv1a("y"), 0))
    feats = feats.at[:, 2].set(jnp.where(
        jax.random.bernoulli(jax.random.fold_in(ks[2], 1), .5, (R,)),
        fnv1a("z"), 0))
    feats = feats.at[:, 3].set(jnp.where(
        jax.random.bernoulli(jax.random.fold_in(ks[3], 1), .5, (R,)),
        fnv1a("m"), 0))
    # flow-key diversity for the hash policies + repeated flows that land
    # on DIFFERENT shards (same key, same pick — reconciliation agreement)
    feats = feats.at[:, 4].set(
        jax.random.randint(jax.random.fold_in(ks[2], 2), (R,), 0, 997))
    if R >= 8:
        feats = feats.at[1::7].set(feats[0])
        svc = svc.at[1::7].set(svc[0])
    mb = jax.random.randint(ks[4], (R,), 1, 500, dtype=jnp.int32)
    tok = jax.random.randint(ks[5], (R,), 2, 90, dtype=jnp.int32)
    rnd = jax.random.randint(ks[6], (R,), 0, 1 << 30, dtype=jnp.int32)
    gum = jax.random.gumbel(ks[7], (R, MAX_EPS_PER_CLUSTER), jnp.float32)
    return RequestBatch(rid, svc, feats, tok, mb), rnd, gum


def _pool(I, C, seed, p_active=0.5):
    act = jax.random.bernoulli(jax.random.PRNGKey(seed), p_active, (I, C))
    return PoolState(jnp.where(act, 100, -1).astype(jnp.int32),
                     jnp.where(act, 0, -1).astype(jnp.int32),
                     jnp.zeros((I, C), jnp.int32),
                     jnp.zeros((I, C), jnp.int32),
                     jnp.zeros((I, C), jnp.int32), act)


def _complete_case(I, C, seed, max_len=8):
    """A completion step with work on every front: mixed EOS/length done,
    inactive lanes, endpoints spread over 4 slots, 2 services, and random
    nonzero health-EWMA bases (the carried accumulators)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    act = jax.random.bernoulli(ks[0], 0.7, (I, C))
    pool = PoolState(
        jnp.where(act, jnp.arange(I * C, dtype=jnp.int32).reshape(I, C),
                  -1).astype(jnp.int32),
        jnp.where(act, jax.random.randint(ks[1], (I, C), 0, 4), -1)
        .astype(jnp.int32),
        jax.random.randint(ks[2], (I, C), 0, 2, dtype=jnp.int32),
        jax.random.randint(ks[3], (I, C), 1, max_len, dtype=jnp.int32),
        jnp.zeros((I, C), jnp.int32), act)
    nxt = jnp.where(jax.random.bernoulli(ks[4], 0.3, (I, C)), 1,
                    jax.random.randint(ks[5], (I, C), 2, 90)
                    ).astype(jnp.int32)
    load = jnp.zeros((MAX_ENDPOINTS,), jnp.int32).at[:4].set(I * C)
    rx = jnp.zeros((MAX_SERVICES,), jnp.int32).at[:2].set(7)
    ewl = jax.random.uniform(ks[6], (MAX_ENDPOINTS,), jnp.float32, 0.0, 5.0)
    ewt = jax.random.uniform(ks[7], (MAX_ENDPOINTS,), jnp.float32, 0.0, 2.0)
    return pool, nxt, load, rx, ewl, ewt


def _assert_same(want, got, ctx=""):
    for name in want._fields:
        w, g = getattr(want, name), getattr(got, name)
        if name == "pool":
            for f in w._fields:
                np.testing.assert_array_equal(
                    np.asarray(getattr(w, f)), np.asarray(getattr(g, f)),
                    err_msg=f"{ctx} pool.{f}")
        else:
            np.testing.assert_array_equal(np.asarray(w), np.asarray(g),
                                          err_msg=f"{ctx} {name}")


# --------------------------------------------------------------------------- #
# in-process (single device): the M=1 mesh + the offset closed forms
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("R,seed", [(64, 7), (33, 3)])
def test_sharded_m1_bit_exact(R, seed):
    """The degenerate 1-way mesh must reproduce ``admit_commit`` exactly:
    same kernel, reconciliation pass reduced to identity psums."""
    st = _rich_state()
    reqs, rnd, gum = _batch(R, seed)
    pool = _pool(4, 3, 9)
    want = ops.admit_commit(reqs, st, pool, rnd, gum)
    got = ops.admit_commit_sharded(reqs, st, pool, rnd, gum,
                                   mesh=make_shard_mesh(1))
    _assert_same(want, got, f"M=1 R={R}")
    assert int(want.held) > 0          # the scenario really exercises holds


def test_sharded_complete_m1_bit_exact():
    """Completion on the degenerate 1-way mesh reproduces the single-shard
    fused kernel exactly — pool writeback, load release, rx, AND the health
    EWMAs (zero-base per-shard deltas + psum + shared f32 epilogue must
    collapse to the in-kernel epilogue at M=1)."""
    max_len = 8
    pool, nxt, load, rx, ewl, ewt = _complete_case(4, 6, seed=23)
    want = ops.complete(pool, nxt, load, rx, ewl, ewt, eos=1,
                        max_len=max_len)
    got = ops.complete_sharded(pool, nxt, load, rx, ewl, ewt,
                               mesh=make_shard_mesh(1),
                               eos=1, max_len=max_len)
    _assert_same(want, got, "complete M=1")
    assert int(np.asarray(want.done_cnt).sum()) > 0


def test_sharded_empty_batch_passthrough():
    st = _rich_state()
    reqs, rnd, gum = _batch(0, 0)
    pool = _pool(4, 3, 9)
    got = ops.admit_commit_sharded(reqs, st, pool, rnd, gum,
                                   mesh=make_shard_mesh(1))
    np.testing.assert_array_equal(np.asarray(got.pool.active),
                                  np.asarray(pool.active))
    np.testing.assert_array_equal(np.asarray(got.ep_load),
                                  np.asarray(st.ep_load))


@pytest.mark.parametrize("seed", range(4))
def test_waterfill_matches_sequential_argmin(seed):
    """The closed-form water-fill (the cross-shard least-request offset)
    equals literally running "argmin over eligible, ties by window offset,
    then increment" k times — for random loads, drains and k."""
    rng = np.random.RandomState(seed)
    n_ep = int(rng.randint(1, 7))
    loads = rng.randint(0, 6, size=n_ep)
    drained = rng.rand(n_ep) < 0.25
    if drained.all():
        drained[rng.randint(n_ep)] = False
    k = int(rng.randint(0, 23))
    st, _ = build_state(
        [ServiceConfig("s", rules=[Rule(0, None, "c")])],
        [Cluster("c", endpoints=list(range(n_ep)),
                 policy=POLICY_LEAST_REQUEST)])
    st = st._replace(
        ep_load=st.ep_load.at[:n_ep].set(jnp.asarray(loads, jnp.int32)),
        ep_drained=st.ep_drained.at[:n_ep].set(
            jnp.asarray(drained, jnp.int32)))
    k_cl = jnp.zeros_like(st.rr_cursor).at[0].set(k)
    got = np.asarray(waterfill_lr(st, k_cl))[:n_ep]
    want = loads.copy()
    elig = np.flatnonzero(~drained)
    for _ in range(k):
        j = elig[int(np.argmin(want[elig]))]
        want[j] += 1
    np.testing.assert_array_equal(got, want,
                                  err_msg=f"loads={loads} k={k} dr={drained}")


def test_admit_sharded_ref_is_shard_major():
    """The oracle's documented merge rule: per-shard rows concatenate in
    shard-major order and the whole thing equals ``admit_commit_ref``."""
    st = _rich_state()
    reqs, rnd, gum = _batch(48, 5)
    pool = _pool(4, 3, 11)
    M, R_loc = 4, 12
    shaped = lambda a: np.asarray(a).reshape(M, R_loc, *a.shape[1:])
    r = ref.admit_sharded_ref(
        shaped(reqs.req_id), shaped(reqs.svc), shaped(reqs.features),
        shaped(reqs.msg_bytes), shaped(reqs.token), st, pool.req_id,
        pool.endpoint, pool.svc, pool.length, pool.token, pool.active,
        shaped(rnd), shaped(gum))
    base = ref.admit_commit_ref(
        reqs.req_id, reqs.svc, reqs.features, reqs.msg_bytes, reqs.token,
        st, pool.req_id, pool.endpoint, pool.svc, pool.length, pool.token,
        pool.active, rnd, gum)
    np.testing.assert_array_equal(r.slot.reshape(-1), base.slot)
    np.testing.assert_array_equal(r.ep_load, base.ep_load)
    np.testing.assert_array_equal(r.pool_active, base.pool_active)
    assert r.cluster.shape == (M, R_loc)


class _FakeMesh:
    """Shape-only stand-in so the 2-way divisibility guard is testable on
    one device (the guard fires before any shard_map is built)."""

    shape = {"shard": 2}


def test_engine_shard_validation():
    from repro.configs import get_config, smoke_config
    from repro.core.interpose import Engine
    from repro.kernels import shard_admit
    cfg = smoke_config(get_config("xlb-service-model"))
    with pytest.raises(ValueError, match="shard_mesh"):
        Engine(cfg, 4, 2, 8, shards=2)
    with pytest.raises(ValueError, match="mesh width"):
        Engine(cfg, 4, 2, 8, shards=2,
               shard_mesh=make_shard_mesh(1))
    with pytest.raises(ValueError, match="divide"):
        Engine(cfg, 3, 2, 8, shards=2, shard_mesh=_FakeMesh())
    # pool instances not divisible over the mesh axis
    reqs, rnd, gum = _batch(8, 0)
    pool = _pool(3, 2, 0)
    with pytest.raises(ValueError, match="divide"):
        shard_admit.admit_commit_sharded(
            reqs.req_id, reqs.svc, reqs.features, reqs.msg_bytes,
            reqs.token, _rich_state(), pool.req_id, pool.endpoint, pool.svc,
            pool.length, pool.token, pool.active, rnd, gum,
            mesh=_FakeMesh())


def test_refresh_plan_pack_unpack_roundtrip():
    """The fan-out wire format: a committed plan survives pack → unpack
    bit-exactly, so a remote sharded consumer applies the identical splice."""
    cp = control.ControlPlane(
        [ServiceConfig("s", rules=[Rule(0, None, "c")])],
        [Cluster("c", endpoints=[0, 1], policy=POLICY_RR)])
    st0 = cp.snapshot()                   # the remote replica, pre-commit
    with cp.transaction():
        cp.add_endpoint("c", 2)
        cp.drain_endpoint("c", 0)
    plan = cp.last_plan
    back = control.unpack_plan(control.pack_plan(plan))
    for a, b in zip(plan.config, back.config):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    np.testing.assert_array_equal(plan.ep_src, back.ep_src)
    np.testing.assert_array_equal(plan.ep_dst, back.ep_dst)
    # journaled plans are versioned (DESIGN.md §11): the splice lands the
    # replica on the control plane's exact version, not a blind +1
    assert back.base_version == 0 and back.version == 1
    st1 = control.apply_plan(st0, back)
    assert int(np.asarray(st1.version)) == cp.version == 1


# --------------------------------------------------------------------------- #
# subprocess: real 4-device mesh (XLA_FLAGS must precede jax init)
# --------------------------------------------------------------------------- #

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["XLB_AUTOTUNE"] = "0"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P

from repro.launch.mesh import make_shard_mesh
from repro.core import control, relay
from repro.core.balancer import PoolState, RequestBatch
from repro.core.routing_table import (MAX_EPS_PER_CLUSTER, N_FEATURES,
    Cluster, POLICY_LEAST_REQUEST, POLICY_RANDOM, POLICY_RR,
    POLICY_WEIGHTED, Rule, ServiceConfig, build_state, fnv1a)
from repro.kernels import ops, ref

import test_shard_admit as T          # PYTHONPATH includes tests/

# --- 1) property sweep: M in {2,4} vs single-shard on the concatenation --- #
scenarios = [
    # (R, seed, pad_slice, pool_seed, p_active, label)
    (96, 7, slice(48, 72), 9, 0.4, "all-padding shard @M=4 + near-full"),
    (96, 3, slice(8, 40), 11, 0.2, "uneven queues (mid-batch padding)"),
    (52, 5, None, 13, 0.6, "ragged R=52 (pads to the shard multiple)"),
]
for R, seed, pad, pseed, pact, label in scenarios:
    st = T._rich_state()
    reqs, rnd, gum = T._batch(R, seed, pad_slice=pad)
    pool = T._pool(4, 5, pseed, p_active=pact)
    want = ops.admit_commit(reqs, st, pool, rnd, gum)
    for M in (2, 4):
        mesh = make_shard_mesh(M)
        got = ops.admit_commit_sharded(reqs, st, pool, rnd, gum, mesh=mesh)
        T._assert_same(want, got, f"M={M} {label}")
    print(f"sweep OK: {label} (held={int(want.held)}, "
          f"no_route={int(want.no_route)})")

# hash policies at volume: the affinity cache fills (intra-batch writes,
# repeated flows split across shards), maglev fallback fires for the
# drained un-rebuilt table slot — and the lowest-shard-wins cache
# reconciliation reproduces the single-shard result bit-exactly
st = T._rich_state()
reqs, rnd, gum = T._batch(128, 41)
pool = T._pool(4, 5, 23, p_active=0.3)
want = ops.admit_commit(reqs, st, pool, rnd, gum)
assert int((np.asarray(want.aff_ep) >= 0).sum()) > 0   # cache populated
for M in (2, 4):
    got = ops.admit_commit_sharded(reqs, st, pool, rnd, gum,
                                   mesh=make_shard_mesh(M))
    T._assert_same(want, got, f"hash policies M={M}")
print("sweep OK: maglev+affinity reconcile bit-exact at M in {2,4}")

# fully-drained cluster is unroutable on every shard
st = T._rich_state()
st = st._replace(ep_drained=st.ep_drained.at[6:8].set(1))  # drain 'rnd'
reqs, rnd, gum = T._batch(64, 21)
pool = T._pool(4, 5, 17)
want = ops.admit_commit(reqs, st, pool, rnd, gum)
got = ops.admit_commit_sharded(reqs, st, pool, rnd, gum,
                               mesh=make_shard_mesh(4))
T._assert_same(want, got, "fully-drained cluster")
print("sweep OK: fully-drained cluster unroutable on every shard")

# --- 1b) completion sharding: M in {2,4}, health EWMAs bit-exact ---------- #
for I, C, seed in ((8, 6, 23), (4, 16, 29)):
    pool, nxt, load, rx, ewl, ewt = T._complete_case(I, C, seed)
    want = ops.complete(pool, nxt, load, rx, ewl, ewt, eos=1, max_len=8)
    for M in (2, 4):
        got = ops.complete_sharded(pool, nxt, load, rx, ewl, ewt,
                                   mesh=make_shard_mesh(M),
                                   eos=1, max_len=8)
        T._assert_same(want, got, f"complete M={M} I={I}")
    assert int(np.asarray(want.done_cnt).sum()) > 0
print("complete OK: sharded health EWMAs bit-exact at M in {2,4}")

# the shard-major oracle pins the sharded op directly
M, R = 4, 64
st = T._rich_state(); reqs, rnd, gum = T._batch(R, 31)
pool = T._pool(4, 5, 19)
sh = lambda a: np.asarray(a).reshape(M, R // M, *a.shape[1:])
r = ref.admit_sharded_ref(sh(reqs.req_id), sh(reqs.svc), sh(reqs.features),
                          sh(reqs.msg_bytes), sh(reqs.token), st,
                          pool.req_id, pool.endpoint, pool.svc, pool.length,
                          pool.token, pool.active, sh(rnd), sh(gum))
got = ops.admit_commit_sharded(reqs, st, pool, rnd, gum,
                               mesh=make_shard_mesh(4))
np.testing.assert_array_equal(r.slot.reshape(-1), np.asarray(got.slot))
np.testing.assert_array_equal(r.ep_load, np.asarray(got.ep_load))
np.testing.assert_array_equal(r.pool_active,
                              np.asarray(got.pool.active).astype(np.int32))
print("oracle OK: admit_sharded_ref pins the 4-shard datapath")

# --- 2) sharded_apply round-trip == dense einsum oracle ------------------- #
mesh = make_shard_mesh(4)
E, C, D, N = 8, 16, 4, 64
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (N, D), jnp.float32)
idx = jax.random.randint(jax.random.PRNGKey(1), (N,), 0, E)
w = jax.random.uniform(jax.random.PRNGKey(2), (N,), jnp.float32)
scale = jnp.arange(1.0, E + 1.0)[:, None]           # per-dest transform

def backend(params, pool):                          # (E_loc, M*C, D)
    return pool * params[:, None, :]

out_sh, meta = jax.jit(jax.shard_map(
    lambda xx, ii, ww, pp: relay.sharded_apply(
        xx, ii, ww, n_dest=E, capacity=C, axis="shard",
        backend_fn=backend, backend_params=pp),
    mesh=mesh, in_specs=(P("shard"), P("shard"), P("shard"), P("shard")),
    out_specs=(P("shard"), relay.RelayMeta(P("shard"), P("shard"),
                                           P("shard"), P(), P())),
    check_vma=False))(x, idx, w, scale)
# dense global oracle at capacity M*C (nothing drops either way)
buf, gmeta, d_oh = relay.relay_dispatch_einsum(x, idx, E, 4 * C)
want = relay.relay_combine_einsum(buf * scale[:, None, :], d_oh, w)
np.testing.assert_allclose(np.asarray(out_sh), np.asarray(want),
                           rtol=1e-5, atol=1e-5)
# meta.load is GLOBAL pre-drop (psum'd), ok per-source (nothing dropped)
np.testing.assert_array_equal(np.asarray(meta.load),
                              np.asarray(jnp.bincount(idx, length=E)))
assert bool(np.all(np.asarray(meta.ok)))
assert float(np.asarray(meta.overflow_frac)) == 0.0
print("relay OK: sharded round-trip matches the einsum oracle, global load")

# --- 3) mid-serve ControlPlane txn -> every sharded consumer, one bump ---- #
from repro.configs import get_config, smoke_config
from repro.core.balancer import make_balancer
from repro.launch.mesh import make_shard_mesh
from repro.models import model as Mmod
from repro.runtime.serve_loop import Request, ServeLoop

cfg = smoke_config(get_config("xlb-service-model"))
params = Mmod.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
cp = control.ControlPlane(
    [ServiceConfig("svc", rules=[Rule(0, None, "pool")])],
    [Cluster("pool", endpoints=[0, 1], policy=POLICY_RR)])
eng = make_balancer("xlb", cfg, 2, 2, 8, shards=2,
                    shard_mesh=make_shard_mesh(2))
loop = ServeLoop(eng, params, cp, admit_batch=4)

class RemoteIngress:
    # a second attached consumer: holds its own replicated routing snapshot
    # and applies the SAME shipped plan pytree (pack/unpack wire format)
    def __init__(self, cp):
        self.routing = cp.snapshot()
    def apply_refresh(self, plan):
        plan = control.unpack_plan(control.pack_plan(plan))
        self.routing = control.apply_plan(self.routing, plan)

remote = RemoteIngress(cp)
cp.attach(remote)
for i in range(4):
    loop.submit(Request(req_id=i, service=0, headers={}, prompt_token=3 + i))
loop.tick()
v0 = int(np.asarray(loop.routing.version))
with cp.transaction():                      # one txn, two deltas
    cp.drain_endpoint("pool", 1)
    cp.set_weight("pool", 0, 2.0)
slot = cp.endpoint_slot("pool", 1)
for name, r in (("loop", loop.routing), ("remote", remote.routing)):
    assert int(np.asarray(r.version)) == v0 + 1, name   # exactly one bump
    assert int(np.asarray(r.ep_drained)[slot]) == 1, name
for i in range(4, 10):
    loop.submit(Request(req_id=i, service=0, headers={}, prompt_token=3 + i))
# pre-drain connections may still sit on the drained endpoint; no POST-
# drain admission (req_id >= 4) may ever land there, on any shard's slice
saw_new = False
for _ in range(30):
    loop.tick()
    pe = np.asarray(loop.state.pool.endpoint)
    pr = np.asarray(loop.state.pool.req_id)
    act = np.asarray(loop.state.pool.active)
    assert not bool(((pe == slot) & (pr >= 4) & act).any())
    saw_new = saw_new or bool(((pr >= 4) & act).any())
assert saw_new                                # traffic kept flowing
print("control OK: one bump on all sharded consumers, drain visible")

# --- 4) transport kill/restart: lease expiry x rejoin resync ------------- #
# A sharded ServeLoop attaches through the lossy plan transport.  It holds
# in-flight load on an endpoint the operator drains, then crashes.  Its
# phantom load must stop pinning the drain once the lease expires, and the
# restarted incarnation must land exactly ONE version-consistent resync.
from repro.runtime import transport
from repro.runtime.serve_loop import Fault, FaultInjector

cp2 = control.ControlPlane(
    [ServiceConfig("svc", rules=[Rule(0, None, "pool")])],
    [Cluster("pool", endpoints=[0, 1], policy=POLICY_RR)],
    lease_epochs=2)
hub = transport.Transport(cp2, transport.LossyChannel(seed=5))
rc = hub.consumer("ingress-0")
# instance 1 wedged: its slots never progress, so its in-flight load can
# only ever clear by the lease expiring (the crash scenario under test)
loop2 = ServeLoop(eng, params, rc, admit_batch=4,
                  fault=FaultInjector([Fault(instance=1, kind="stall")]))
t = [0]
def pump(n, dead=False):
    for _ in range(n):
        hub.pump(t[0])
        if not dead:
            loop2.tick()
        t[0] += 1
for i in range(6):
    loop2.submit(Request(req_id=200 + i, service=0, headers={},
                         prompt_token=3 + i))
pump(4)                                       # admit + heartbeat the load in
cp2.drain_endpoint("pool", 1)
pump(3)                                       # plan v1 ships + lands
slot1 = cp2.endpoint_slot("pool", 1)
assert rc.version == cp2.version == 1
assert int(np.asarray(loop2.routing.ep_drained)[slot1]) == 1
proxy = hub.publisher.nodes["ingress-0"].proxy
assert int(proxy.routing.ep_load[slot1]) > 0  # reported load pins the row
cp2.reap()
assert len(cp2.cluster_members("pool")) == 2  # live lease: reap blocked
assert cp2.version == 1                       # blocked reap = no commit
rc.crash()                                    # the host dies mid-drain
# 4 epochs, not lease_epochs+1: a heartbeat already in flight through the
# lossy channel can land after the first advance and refresh the lease one
# epoch later than the crash tick
for _ in range(4):
    cp2.advance_epoch()
    pump(1, dead=True)
assert not cp2.lease_live(proxy)              # lease expired
cp2.reap()                                    # phantom load ignored now
assert len(cp2.cluster_members("pool")) == 1
assert cp2.version == 2
cp2.set_weight("pool", 0, 2.0)                # commits keep landing while
assert cp2.version == 3                       # the node is dead
pump(4, dead=True)                            # dead node: nothing ships
assert hub.publisher.nodes["ingress-0"].acked == 1
rc.restart()                                  # fresh process, version -1
loop3 = ServeLoop(eng, params, rc, admit_batch=4)
for _ in range(12):
    hub.pump(t[0]); loop3.tick(); t[0] += 1
assert rc.resyncs == 1, rc.resyncs            # exactly one resync
assert rc.version == cp2.version == 3
transport.assert_converged(cp2, [rc])
for i in range(4):
    loop3.submit(Request(req_id=300 + i, service=0, headers={},
                         prompt_token=3))
for _ in range(20):
    hub.pump(t[0]); loop3.tick(); t[0] += 1
assert len(loop3.done) == 4                   # resumed serving post-rejoin
print("transport OK: lease unpinned the phantom drain, one resync on rejoin")
"""


@pytest.mark.timeout(900)
def test_sharded_admission_subprocess():
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["PYTHONPATH"] = "src" + os.pathsep + here
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=850,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, \
        f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-4000:]}"
    for marker in ("sweep OK: all-padding shard",
                   "sweep OK: uneven queues",
                   "sweep OK: ragged R=52",
                   "sweep OK: maglev+affinity reconcile",
                   "sweep OK: fully-drained cluster",
                   "complete OK: sharded health EWMAs",
                   "oracle OK: admit_sharded_ref",
                   "relay OK: sharded round-trip",
                   "control OK: one bump",
                   "transport OK: lease unpinned the phantom drain"):
        assert marker in out.stdout, f"missing {marker!r}\n{out.stdout}"
