"""Sidecar baselines — the architectures XLB replaces (paper Fig. 1 a/b).

Both baselines implement the exact :class:`repro.core.balancer.Balancer`
protocol the XLB engine implements (init_state / admit / step / make_jitted
over I×C instance pools) but place the LB where Istio/Cilium place the
proxy:

  * ``IstioEngine``  — a *per-instance proxy*: every instance lane is its own
    compiled program with its own cache; the host router inspects every
    response, re-routes, and re-launches per-instance programs each step.
    Overheads reproduced: per-hop host↔device copies (syscalls / kernel stack
    traversals), per-instance dispatch (cross-process scheduling), duplicate
    routing work (duplicate protocol processing).
  * ``CiliumEngine`` — a *global proxy*: one compiled program for all lanes
    (sockmap-style shortcut) but routing/admission still runs on the host, so
    each step still pays one host round-trip and the python LB.

The XLB engine (core/interpose.py) removes all of the above by compiling
admission + decode into a single on-device program.  Because all three
implement one protocol, ``ServeLoop`` / ``launch/serve.py --engine`` /
``benchmarks`` drive them with zero per-engine glue, and a ControlPlane
transaction reaches the host router through the same ``apply_refresh`` seam
(the pre-refresh private numpy copy that silently diverged is gone: the
router's tables are refreshed in place, loads migrated, pool references
remapped).
"""

from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import control, policy_defs
from repro.core.balancer import PoolState, RequestBatch
from repro.core.routing_table import (MAX_SERVICES, FlowMetrics,
                                      RoutingState)
from repro.kernels.completion import RX_BYTES_PER_TOKEN, health_update
from repro.models import model as M
from repro.models.transformer import DEFAULT_CTX


class HostRouter:
    """The user-space LB logic of the proxy (numpy, per-request python).

    Holds the proxy's routing tables as host numpy arrays; ``refresh``
    adopts a new control-plane snapshot (the caller migrates mutable state
    through the plan before handing it over)."""

    def __init__(self, routing: RoutingState, seed: int = 0):
        self.t = jax.tree.map(lambda a: np.array(a, copy=True), routing)
        self.rng = np.random.RandomState(seed)

    def refresh(self, routing: RoutingState) -> None:
        self.t = jax.tree.map(lambda a: np.array(a, copy=True), routing)

    def match(self, svc: int, features: np.ndarray) -> int:
        t = self.t
        start, count = int(t.svc_rule_start[svc]), int(t.svc_rule_count[svc])
        for r in range(start, start + count):
            exp = int(t.rule_value[r])
            if exp == -1 or exp == int(features[int(t.rule_field[r])]):
                return int(t.rule_cluster[r])
        return -1

    def select(self, cluster: int,
               features: np.ndarray | None = None) -> tuple[int, int]:
        t = self.t
        start, count = (int(t.cluster_ep_start[cluster]),
                        int(t.cluster_ep_count[cluster]))
        # the ControlPlane's draining mask gates selection under every
        # policy (same eligibility rule as the fused kernel / staged path);
        # a cluster whose endpoints are all draining is unroutable.  The
        # no-drain steady state takes a vectorized fast path — the same
        # shortcut the kernel's segment fold takes via lax.cond — instead
        # of a per-slot python filter on every pick.
        if count == 0:
            return -1, -1
        window = t.ep_drained[start:start + count]
        if window.any():
            elig = [start + j for j in range(count) if not window[j]]
            if not elig:
                return -1, -1
        else:
            elig = list(range(start, start + count))
        # registry dispatch (DESIGN.md §9): the host lowering of whichever
        # policy the cluster runs; hash-keyed policies (maglev/affinity)
        # select on the request features' flow id
        pol = int(t.cluster_policy[cluster])
        pdef = policy_defs.BY_ENUM.get(pol, policy_defs.BY_ENUM[0])
        feats = (np.zeros((1,), np.int32) if features is None
                 else np.asarray(features, np.int32))
        ep = int(pdef.host_pick(self, cluster, elig, feats))
        t.ep_load[ep] += 1
        return ep, int(t.ep_instance[ep])

    def release(self, ep: int) -> None:
        if ep >= 0:
            self.t.ep_load[ep] -= 1


class SidecarState(NamedTuple):
    """Host-resident engine state: same shape contract as ``EngineState``,
    numpy residency (every field the host proxy touches stays on the host —
    that *is* the baseline's overhead)."""

    router: HostRouter
    pool: PoolState          # numpy arrays, mutated in place
    caches: Any              # list of per-instance caches (istio) | one
    metrics: FlowMetrics     # numpy arrays, mutated in place


def _np_pool(I: int, C: int) -> PoolState:
    return PoolState(
        req_id=np.full((I, C), -1, np.int32),
        endpoint=np.full((I, C), -1, np.int32),
        svc=np.zeros((I, C), np.int32),
        length=np.zeros((I, C), np.int32),
        token=np.zeros((I, C), np.int32),
        active=np.zeros((I, C), bool),
    )


def _np_metrics() -> FlowMetrics:
    return FlowMetrics(
        tx_bytes=np.zeros((MAX_SERVICES,), np.int64),
        rx_bytes=np.zeros((MAX_SERVICES,), np.int64),
        requests=np.zeros((MAX_SERVICES,), np.int64),
        no_route_match=np.zeros((), np.int64),
        overflow=np.zeros((), np.int64),
    )


@dataclasses.dataclass
class SidecarEngine:
    """Host-interposed serving engine (mode: 'istio' | 'cilium')."""

    cfg: ModelConfig
    n_instances: int
    slots: int
    max_len: int
    mode: str = "istio"
    eos: int = 1
    ctx: Any = DEFAULT_CTX

    def __post_init__(self):
        cfg, ctx = self.cfg, self.ctx

        @jax.jit
        def decode(params, tokens, lengths, cache):
            logits, cache = M.decode_step(cfg, params, tokens, lengths, cache,
                                          ctx=ctx)
            return jnp.argmax(logits, -1).astype(jnp.int32), cache

        self._decode = decode

    # ------------------------------------------------------------------ #
    def init_state(self, routing: RoutingState, dtype=None) -> SidecarState:
        I, C = self.n_instances, self.slots
        dtype = dtype or jnp.float32
        if self.mode == "istio":
            # one cache + one compiled program PER instance (per-svc proxy)
            caches = [M.init_cache(self.cfg, C, self.max_len, dtype)
                      for _ in range(I)]
        else:
            caches = M.init_cache(self.cfg, I * C, self.max_len, dtype)
        return SidecarState(HostRouter(routing), _np_pool(I, C), caches,
                            _np_metrics())

    # ------------------------------------------------------------------ #
    def admit(self, state: SidecarState, reqs: RequestBatch) -> SidecarState:
        """Host-side routing + slot allocation (per-request python)."""
        router, pool, m = state.router, state.pool, state.metrics
        req_id = np.asarray(reqs.req_id)
        svc = np.asarray(reqs.svc)
        feats = np.asarray(reqs.features)
        tok = np.asarray(reqs.token)
        nbytes = np.asarray(reqs.msg_bytes)
        for r in range(len(req_id)):
            if req_id[r] < 0:
                continue
            cluster = router.match(int(svc[r]), feats[r])
            if cluster < 0:
                m.no_route_match[...] += 1
                continue
            ep, inst = router.select(cluster, feats[r])
            if inst < 0:
                continue
            free = np.where(~pool.active[inst])[0]
            if len(free) == 0:                   # held (pool exhausted)
                router.release(ep)
                m.overflow[...] += 1
                continue
            s = int(free[0])
            pool.req_id[inst, s] = req_id[r]
            pool.endpoint[inst, s] = ep
            pool.svc[inst, s] = svc[r]
            pool.length[inst, s] = 0
            pool.token[inst, s] = tok[r]
            pool.active[inst, s] = True
            if svc[r] < MAX_SERVICES:
                m.requests[svc[r]] += 1
                m.tx_bytes[svc[r]] += nbytes[r]
        return state

    # ------------------------------------------------------------------ #
    def step(self, params, state: SidecarState) -> tuple[SidecarState, dict]:
        """One decode step for all lanes, host-mediated."""
        I, C = self.n_instances, self.slots
        router, pool, m = state.router, state.pool, state.metrics
        caches = state.caches
        if self.mode == "istio":
            nxt = np.zeros((I, C), np.int32)
            for i in range(I):                   # per-instance program launch
                toks = jnp.asarray(pool.token[i][:, None], jnp.int32)
                lens = jnp.asarray(pool.length[i], jnp.int32)
                out, caches[i] = self._decode(params, toks, lens, caches[i])
                nxt[i] = np.asarray(out)         # proxy reads every response
        else:
            toks = jnp.asarray(pool.token.reshape(-1, 1), jnp.int32)
            lens = jnp.asarray(pool.length.reshape(-1), jnp.int32)
            out, caches = self._decode(params, toks, lens, caches)
            nxt = np.asarray(out).reshape(I, C)  # one global proxy round-trip
        state = state._replace(caches=caches)

        # vectorised host bookkeeping (numpy): keeps the baseline honest — the
        # architectural cost we measure is the per-request python ROUTING and
        # (for istio) per-instance program launches, not sloppy loops.
        pre_req = pool.req_id.copy()             # ids serviced this tick
        act = pool.active.copy()
        pool.length[act] += 1
        pool.token[act] = nxt[act]
        np.add.at(m.rx_bytes, np.maximum(pool.svc[act], 0),
                  RX_BYTES_PER_TOKEN)
        done = act & ((nxt == self.eos) | (pool.length >= self.max_len - 1))
        # health EWMAs: same shared epilogue as the fused kernel, on the
        # same integer observations (occupancy before release, completions
        # per endpoint) — host-resident parity for the closed loop
        E = router.t.ep_load.shape[0]
        occ0 = router.t.ep_load.astype(np.int32).copy()
        cnt = np.zeros((E,), np.int32)
        eps = pool.endpoint[done]
        np.add.at(cnt, eps[(eps >= 0) & (eps < E)], 1)
        ewl, ewt = health_update(jnp.asarray(router.t.ep_inflight_ewma),
                                 jnp.asarray(router.t.ep_tput_ewma),
                                 jnp.asarray(occ0), jnp.asarray(cnt))
        router.t.ep_inflight_ewma[...] = np.asarray(ewl)
        router.t.ep_tput_ewma[...] = np.asarray(ewt)
        for ep in pool.endpoint[done]:           # release load counters
            router.release(int(ep))
        pool.active[done] = False
        pool.req_id[done] = -1
        pool.endpoint[done] = -1
        pool.length[done] = 0
        active = int(act.sum() - done.sum())
        out = {"emitted": nxt, "done": done, "req_id": pre_req,
               "active": active,
               "wire": np.concatenate([
                   nxt.ravel(), done.astype(np.int32).ravel(),
                   pre_req.ravel(), np.array([active], np.int32)])}
        return state, out

    # ------------------------------------------------------------------ #
    def make_jitted(self, donate: bool = True):
        """Protocol parity with ``Engine.make_jitted``: the returned
        ``serve_step`` has the same signature, but only the decode inside is
        compiled — admission stays a host round-trip, which is the point."""

        def serve_step(params, state: SidecarState, reqs: RequestBatch):
            if np.any(np.asarray(reqs.req_id) >= 0):
                state = self.admit(state, reqs)
            return self.step(params, state)

        return serve_step

    # ------------------------------------------------------------------ #
    # control-plane seam (Balancer protocol)
    # ------------------------------------------------------------------ #
    def get_routing(self, state: SidecarState) -> RoutingState:
        return state.router.t

    def apply_refresh(self, state: SidecarState,
                      plan: control.RefreshPlan) -> SidecarState:
        """Adopt a committed transaction: same plan splice as the in-graph
        engine (config swap + load migration), then remap the host pool's
        endpoint references in place."""
        state.router.refresh(control.apply_plan(state.router.t, plan))
        pe = state.pool.endpoint
        pe[...] = np.asarray(control.remap_endpoints(plan, pe))
        return state


@dataclasses.dataclass
class IstioEngine(SidecarEngine):
    """Per-instance sidecar proxy (paper Fig. 1a)."""

    mode: str = "istio"


@dataclasses.dataclass
class CiliumEngine(SidecarEngine):
    """Shared global proxy (paper Fig. 1b)."""

    mode: str = "cilium"
