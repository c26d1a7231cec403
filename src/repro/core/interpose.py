"""In-graph interposition — the XLB serving engine (paper §3/§4).

The engine owns ``I`` instance lanes × ``C`` decode slots (the pre-established
i-sock pools).  Both engine operations are compiled *into* the model program —
the LB is a logical extension of the application:

  * ``admit``  — connection establishment + load balancing: content match →
    policy select → slot allocation → pool commit, all inside one Pallas
    kernel (kernels/route_match.py::admit_commit).  No host round-trip:
    the paper's "client TCP connection is bypassed".
  * ``step``   — one decode step for every active slot across all lanes in a
    single batched program, then completion handling (done detect, load
    release, rx metrics, slot free) as one fused Pallas kernel
    (kernels/completion.py::complete).

``Engine`` implements the :class:`repro.core.balancer.Balancer` protocol —
the same contract the sidecar baselines in core/sidecar.py implement with
host-mediated routing + per-instance programs (the overhead classes of paper
Table 2).  Control-plane transactions (core/control.py) reach a running
engine through ``apply_refresh``: config tables swap, loads migrate, pool
endpoint references remap — all without recompiling ``serve_step``.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import control
from repro.core.balancer import PoolState, RequestBatch  # noqa: F401 (re-export:
# RequestBatch/PoolState moved to core.balancer; importers keep working)
from repro.core.routing_table import (MAX_EPS_PER_CLUSTER, FlowMetrics,
                                      RoutingState)
from repro.kernels import ops
from repro.models import model as M
from repro.models.transformer import DEFAULT_CTX


class EngineState(NamedTuple):
    routing: RoutingState
    pool: PoolState
    cache: Any             # model KV/SSM cache, batch dim = I*C
    metrics: FlowMetrics
    key: jax.Array


@dataclasses.dataclass(frozen=True)
class Engine:
    """XLB in-graph serving engine for one service fleet."""

    cfg: ModelConfig
    n_instances: int
    slots: int
    max_len: int
    eos: int = 1
    ctx: Any = DEFAULT_CTX
    # kernel tuning overrides; None = the autotuned plan (kernels/tune.py)
    block_r: int | None = None
    block_i: int | None = None
    fold: str | None = None
    # mesh-sharded admission (DESIGN.md §7): with shards > 1 the admit
    # batch splits (R/M,) and the pool (I/M,) over ``shard_axis`` of
    # ``shard_mesh``, the fused kernel runs per shard, and one collective
    # pass reconciles — bit-exact vs the single-shard path on the same
    # batch.  Requires n_instances % shards == 0 and a mesh with >= shards
    # devices (launch/mesh.py::make_shard_mesh).
    shards: int = 1
    shard_mesh: Any = None
    shard_axis: str = "shard"

    def __post_init__(self):
        if self.shards > 1:
            if self.shard_mesh is None:
                raise ValueError("shards > 1 needs a shard_mesh "
                                 "(launch/mesh.py::make_shard_mesh)")
            mesh_m = self.shard_mesh.shape[self.shard_axis]
            if mesh_m != self.shards:
                raise ValueError(
                    f"shards={self.shards} but shard_mesh axis "
                    f"{self.shard_axis!r} is {mesh_m}-way — the datapath "
                    "would silently shard at the mesh width")
            if self.n_instances % self.shards:
                raise ValueError(f"n_instances ({self.n_instances}) must "
                                 f"divide over {self.shards} shards")

    # ------------------------------------------------------------------ #
    def init_state(self, routing: RoutingState, dtype=None) -> EngineState:
        return EngineState(
            routing=routing,
            pool=PoolState.init(self.n_instances, self.slots),
            cache=M.init_cache(self.cfg, self.n_instances * self.slots,
                               self.max_len, dtype),
            metrics=FlowMetrics.zeros(),
            key=jax.random.PRNGKey(0),
        )

    # ------------------------------------------------------------------ #
    # admit: routing + balancing + slot allocation + pool commit — one
    # fused Pallas kernel (route → balance → slot-allocate → pool write →
    # metrics), the paper's single in-kernel tail-call chain ending in the
    # sockmap update.  The staged jnp chain lives on in core/router.py +
    # core/policies.py + core/request_map.py (the sidecar baselines and
    # the bench_admit comparison drive it from there).
    # ------------------------------------------------------------------ #
    def admit(self, state: EngineState, reqs: RequestBatch) -> EngineState:
        rstate, metrics = state.routing, state.metrics
        key, sub = jax.random.split(state.key)
        kr, kw, _ = jax.random.split(sub, 3)
        R = reqs.req_id.shape[0]
        # host PRNG draws feed the kernel so random/weighted stay on the
        # engine's key stream (and match the admit_ref oracle bit-exactly)
        rnd = jax.random.randint(kr, (R,), 0, 1 << 30, dtype=jnp.int32)
        gumbel = jax.random.gumbel(kw, (R, MAX_EPS_PER_CLUSTER), jnp.float32)

        if self.shards > 1:
            res = ops.admit_commit_sharded(
                reqs, rstate, state.pool, rnd, gumbel, mesh=self.shard_mesh,
                axis=self.shard_axis, block_r=self.block_r, fold=self.fold)
        else:
            res = ops.admit_commit(reqs, rstate, state.pool, rnd, gumbel,
                                   block_r=self.block_r, fold=self.fold)
        # the committed pool, load counters, rr cursors, affinity cache,
        # held release and flow metrics all come fused out of the kernel
        rstate = rstate._replace(ep_load=res.ep_load, rr_cursor=res.rr_cursor,
                                 aff_key=res.aff_key, aff_ep=res.aff_ep)
        metrics = metrics._replace(
            requests=metrics.requests + res.svc_requests,
            tx_bytes=metrics.tx_bytes + res.svc_tx_bytes,
            no_route_match=metrics.no_route_match + res.no_route,
            # per-ATTEMPT hold events: a request the host re-queues and
            # re-admits counts once per attempt (FlowMetrics docstring);
            # distinct held requests live on the host (ServeLoop.held_first)
            overflow=metrics.overflow + res.held,
        )
        return EngineState(rstate, res.pool, state.cache, metrics, key)

    # ------------------------------------------------------------------ #
    # step: one batched decode over all lanes; completion handling (done
    # detect → load release → rx metrics → slot free) runs as one fused
    # Pallas kernel over the (I, C) pool — the paper's in-kernel close
    # path.  The staged jnp chain it replaced is kept as the baseline in
    # benchmarks/run.py::bench_step.
    # ------------------------------------------------------------------ #
    def step(self, params, state: EngineState) -> tuple[EngineState, dict]:
        pool, cache = state.pool, state.cache
        I, C = pool.req_id.shape
        B = I * C
        tokens = pool.token.reshape(B, 1)
        lengths = pool.length.reshape(B)
        with jax.named_scope("xlb_decode"):
            logits, cache = M.decode_step(self.cfg, params, tokens, lengths,
                                          cache, ctx=self.ctx)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32).reshape(I, C)

        if self.shards > 1:
            res = ops.complete_sharded(
                pool, nxt, state.routing.ep_load, state.metrics.rx_bytes,
                state.routing.ep_inflight_ewma, state.routing.ep_tput_ewma,
                mesh=self.shard_mesh, axis=self.shard_axis,
                eos=self.eos, max_len=self.max_len,
                block_i=self.block_i, fold=self.fold)
        else:
            res = ops.complete(pool, nxt, state.routing.ep_load,
                               state.metrics.rx_bytes,
                               state.routing.ep_inflight_ewma,
                               state.routing.ep_tput_ewma,
                               eos=self.eos, max_len=self.max_len,
                               block_i=self.block_i, fold=self.fold)
        rstate = state.routing._replace(ep_load=res.ep_load,
                                        ep_inflight_ewma=res.ep_inflight_ewma,
                                        ep_tput_ewma=res.ep_tput_ewma)
        metrics = state.metrics._replace(rx_bytes=res.rx_bytes)
        active = res.pool.active.sum(dtype=jnp.int32)
        out = {"emitted": nxt, "done": res.done,
               "req_id": state.pool.req_id,     # ids that produced this tick
               "active": active,
               # all four packed in one vector: the host pays one
               # device→host transfer a tick instead of four
               "wire": jnp.concatenate([
                   nxt.ravel(), res.done.astype(jnp.int32).ravel(),
                   state.pool.req_id.ravel(), active[None]])}
        return EngineState(rstate, res.pool, cache, metrics, state.key), out

    # ------------------------------------------------------------------ #
    def make_jitted(self, donate: bool = True):
        """One fused program: admit + decode step (the XLB datapath).

        Admission is gated by ``lax.cond`` on "any arrivals", so steady-state
        decode ticks skip the routing/allocation work entirely (the paper's
        connect-path eBPF hook only fires on connect).  The gate, its
        predicate and the whole admission branch run under the named scope
        ``xlb_admit``; the decode runs under ``xlb_decode`` (``step``)."""

        @partial(jax.jit, donate_argnums=(1,) if donate else ())
        def serve_step(params, state: EngineState, reqs: RequestBatch):
            with jax.named_scope("xlb_admit"):
                state = jax.lax.cond(jnp.any(reqs.req_id >= 0),
                                     lambda s: self.admit(s, reqs),
                                     lambda s: s, state)
            return self.step(params, state)

        from repro.analysis.invariants import sanitize_enabled
        if not sanitize_enabled():
            return serve_step

        # XLB_SANITIZE=1: the kernel wrappers emit conservation-law checks
        # into the trace (analysis/invariants.py::guard); functionalize them
        # here — the host boundary — and fail the tick loudly on violation.
        from jax.experimental import checkify
        ck = jax.jit(checkify.checkify(serve_step,
                                       errors=checkify.user_checks))

        def sanitized_step(params, state, reqs):
            err, res = ck(params, state, reqs)
            err.throw()
            return res

        sanitized_step._cache_size = ck._cache_size   # recompile probes
        return sanitized_step

    # ------------------------------------------------------------------ #
    # control-plane seam (Balancer protocol)
    # ------------------------------------------------------------------ #
    def get_routing(self, state: EngineState) -> RoutingState:
        return state.routing

    def apply_refresh(self, state: EngineState,
                      plan: control.RefreshPlan) -> EngineState:
        """Splice a committed transaction into the live state: one buffer
        swap for the tables (load counters migrate through the slot
        permutation) and a remap of the pool's endpoint references, so
        completions of in-flight connections release the counter of the
        endpoint's *new* slot — never a new occupant of its old one."""
        routing = control.apply_plan(state.routing, plan)
        pool = state.pool._replace(
            endpoint=control.remap_endpoints(plan, state.pool.endpoint))
        return state._replace(routing=routing, pool=pool)
