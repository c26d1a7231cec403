"""Host serving driver: ingress parsing + continuous batching around any
:class:`repro.core.balancer.Balancer` — the XLB in-graph engine or either
sidecar baseline, with zero per-engine glue.

The host does exactly what the paper leaves outside eBPF (its helper
functions): byte-level protocol parsing — here hashing L7 header fields into
the fixed int32 feature vector — and queueing.  Everything else (routing,
balancing, slot allocation, decode) runs wherever the engine places it.

Routing can be given as a plain ``RoutingState`` snapshot or as a
``ControlPlane``; with a ControlPlane the loop attaches itself, so every
committed transaction reaches the live engine state mid-serve (config swap,
load migration, pool remap) without recompiling the datapath.
"""

from __future__ import annotations

import collections
import dataclasses
import heapq
import time
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.analysis.invariants import assert_host, sanitize_enabled
from repro.core import control
from repro.core.balancer import Balancer, RequestBatch
from repro.core.routing_table import N_FEATURES, RoutingState, fnv1a
from repro.runtime import transport


@dataclasses.dataclass
class Request:
    req_id: int
    service: int
    headers: dict[str, str]
    prompt_token: int
    msg_bytes: int = 128
    t_submit: float = 0.0
    t_done: float = 0.0
    retries: int = 0
    hop: int = 0                # chain position (workload/chain.py): which
    #                             service of a call chain this admission is
    tokens: list = dataclasses.field(default_factory=list)


class DrainReport(NamedTuple):
    """What a drain actually left behind — not just the completions."""

    done: list            # completed Requests (all-time, == loop.done)
    dropped: list         # gave up after max retries (== loop.dropped)
    queued: int           # still waiting at the ingress (ready queue +
    #                       backoff set) when draining ended
    inflight: int         # still holding a pool slot when draining ended
    held_first: int = 0   # DISTINCT requests ever re-queued (held or
    #                       unroutable) — each counts once, however many
    #                       attempts it took; the engine's metrics.overflow
    #                       counts per-ATTEMPT hold events (FlowMetrics)


# --------------------------------------------------------------------------- #
# Fault injection — the degraded-scenario harness (DESIGN.md §8)
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class Fault:
    """One injected endpoint fault, in engine ticks.

    Faults act on *progress*, not on routing: on a held tick the instance's
    active slots have their decode position rolled back by one, so the step
    the engine just took (or is about to take) nets to zero — requests pile
    up, occupancy rises, completions stop.  That is exactly what a slow or
    wedged backend looks like from the datapath, and it is invisible to any
    per-request length bookkeeping — only the occupancy/throughput EWMAs
    (kernels/completion.py::health_update) can see it.

      slow   — the instance makes net progress on 1 tick in ``factor``
               (a ×factor slowdown)
      stall  — no progress at all while the fault is active
      flap   — alternates ``period`` stalled ticks / ``period`` healthy
               ticks (the breaker-hysteresis stressor)
    """

    instance: int
    kind: str = "slow"          # slow | stall | flap
    factor: int = 10
    start: int = 0
    end: int | None = None      # None = never clears
    period: int = 8             # flap half-cycle, in ticks

    def holds(self, tick: int) -> bool:
        """Does this fault hold the instance's progress at ``tick``?"""
        if tick < self.start or (self.end is not None and tick >= self.end):
            return False
        if self.kind == "stall":
            return True
        if self.kind == "slow":
            return (tick - self.start) % self.factor != 0
        if self.kind == "flap":
            return ((tick - self.start) // self.period) % 2 == 0
        raise ValueError(f"unknown fault kind {self.kind!r}")


class FaultInjector:
    """Applies a set of :class:`Fault` schedules to a live pool.

    ``apply`` runs on the host between engine ticks and rolls back
    ``pool.length`` on the held instances' active slots (floored at 0).
    Works on both pool representations: the XLB engine's jax arrays
    (functional update) and the sidecar's numpy pool (in-place)."""

    def __init__(self, faults):
        self.faults = list(faults)

    def active(self, tick: int) -> list[int]:
        return [f.instance for f in self.faults if f.holds(tick)]

    def clear_tick(self) -> int | None:
        """Last tick at which any fault clears (None if one never does)."""
        ends = [f.end for f in self.faults]
        return None if any(e is None for e in ends) else max(ends, default=0)

    def apply(self, pool, tick: int):
        # clamp against the live instance window: a fault schedule written
        # for a larger fleet (or racing an elastic scale event on the same
        # tick) may name an instance lane the pool no longer has — numpy
        # pools would IndexError, jax pools would silently clip to the last
        # lane and hold the wrong instance.  Out-of-window faults are inert.
        I = pool.length.shape[0]
        held = [i for i in self.active(tick) if 0 <= i < I]
        if not held:
            return pool
        if isinstance(pool.length, np.ndarray):
            for i in held:
                m = pool.active[i] & (pool.length[i] > 0)
                pool.length[i, m] -= 1
            return pool
        length = pool.length
        for i in held:
            m = pool.active[i] & (length[i] > 0)
            length = length.at[i].add(jnp.where(m, -1, 0))
        return pool._replace(length=length)


def parse_features(headers: dict[str, str]) -> np.ndarray:
    """Host ingress 'protocol parse': hash selected header fields into the
    feature vector the in-graph router matches on."""
    feats = np.zeros((N_FEATURES,), np.int32)
    for i, field in enumerate(("path", "user", "version", "tenant",
                               "method", "content-type", "region", "abtest")):
        if field in headers:
            feats[i] = fnv1a(headers[field])
    return feats


class ServeLoop:
    """Continuous batching driver for one service fleet."""

    def __init__(self, balancer: Balancer, params,
                 routing: RoutingState | control.ControlPlane
                 | transport.RemoteConsumer,
                 admit_batch: int = 8, dtype=jnp.float32,
                 max_retries: int = 64, backoff_base: int = 1,
                 backoff_cap: int = 16, backoff_seed: int = 0,
                 fault: FaultInjector | None = None):
        self.balancer = balancer
        self.params = params
        self.admit_batch = admit_batch
        self.cp = None
        self.remote = None
        if isinstance(routing, control.ControlPlane):
            cp, routing = routing, routing.snapshot()
            cp.attach(self)
            self.cp = cp
        elif isinstance(routing, transport.RemoteConsumer):
            # attach through the plan transport instead of in-process: the
            # consumer pumps its lossy channel each tick (plans in,
            # heartbeat + live load report out) and calls apply_refresh
            # here; the loop boots at whatever snapshot the consumer was
            # seeded with (runtime/transport.py).
            rc, routing = routing, routing.boot_routing
            rc.bind(self)
            self.remote = rc
        self.state = balancer.init_state(routing, dtype=dtype)
        self.serve_step = balancer.make_jitted(donate=False)
        self.queue: collections.deque[Request] = collections.deque()
        self.inflight: dict[int, Request] = {}
        self.done: list[Request] = []
        self.dropped: list[Request] = []    # gave up after max retries
        self.held_first = 0                 # distinct requests ever re-queued
        #                                     (first attempt only — the
        #                                     engine's overflow metric counts
        #                                     every attempt, FlowMetrics doc)
        # Held/unroutable requests back off with capped exponential delay +
        # deterministic jitter instead of hammering the admit path every
        # tick: delay_k = min(base·2^(k-1), cap) + U[0, delay_k), the jitter
        # drawn from a PRNG seeded by (seed, req_id, attempt) so replays are
        # bit-identical while concurrent requests still de-synchronize.
        self.max_retries = max_retries
        self.backoff_base = backoff_base
        self.backoff_cap = backoff_cap
        self.backoff_seed = backoff_seed
        self._waiting: list[tuple[int, int, Request]] = []   # backoff heap:
        self._wseq = 0                      # (eligible_tick, seq, Request)
        self.ticks = 0                      # engine ticks driven so far
        self.fault = fault                  # optional FaultInjector
        self.submitted = 0                  # all-time submit() count (the
        #                                     queue-conservation law input)

    # ------------------------------------------------------------------ #
    # control-plane seam
    # ------------------------------------------------------------------ #
    @property
    def routing(self) -> RoutingState:
        """The live routing tables the engine is reading right now."""
        return self.balancer.get_routing(self.state)

    def apply_refresh(self, plan: control.RefreshPlan) -> None:
        """ControlPlane consumer hook: splice a committed transaction into
        the live engine state (same compiled datapath, new tables)."""
        self.state = self.balancer.apply_refresh(self.state, plan)

    # ------------------------------------------------------------------ #
    @property
    def n_queued(self) -> int:
        """Everything still at the ingress: ready queue + backoff set.
        ``submitted == done + dropped + n_queued + inflight`` at all times."""
        return len(self.queue) + len(self._waiting)

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        self.submitted += 1
        self.queue.append(req)

    def _backoff(self, req: Request) -> None:
        """Park a held request until its retry matures (or drop it)."""
        if req.retries >= self.max_retries:
            req.t_done = time.perf_counter()     # unroutable requests drop,
            self.dropped.append(req)             # but stay accounted
            return
        delay = min(self.backoff_base << (req.retries - 1), self.backoff_cap)
        rng = np.random.default_rng(
            (self.backoff_seed, req.req_id, req.retries))
        delay += int(rng.integers(0, delay))
        heapq.heappush(self._waiting,
                       (self.ticks + delay, self._wseq, req))
        self._wseq += 1

    def _release_matured(self) -> None:
        """Move matured backoff entries to the FRONT of the ready queue
        (oldest eligible first) — held work keeps priority over new
        arrivals, as with the old immediate re-queue."""
        batch = []
        while self._waiting and self._waiting[0][0] <= self.ticks:
            batch.append(heapq.heappop(self._waiting)[2])
        self.queue.extendleft(reversed(batch))

    def _next_admission(self) -> tuple[RequestBatch, list]:
        R = self.admit_batch
        rid = np.full((R,), -1, np.int32)
        svc = np.zeros((R,), np.int32)
        feats = np.zeros((R, N_FEATURES), np.int32)
        tok = np.zeros((R,), np.int32)
        nbytes = np.zeros((R,), np.int32)
        taken = []
        for i in range(R):
            if not self.queue:
                break
            r = self.queue.popleft()
            rid[i], svc[i] = r.req_id, r.service
            feats[i] = parse_features(r.headers)
            tok[i], nbytes[i] = r.prompt_token, r.msg_bytes
            self.inflight[r.req_id] = r
            taken.append(r)
        return RequestBatch(
            req_id=jnp.asarray(rid), svc=jnp.asarray(svc),
            features=jnp.asarray(feats), token=jnp.asarray(tok),
            msg_bytes=jnp.asarray(nbytes)), taken

    # ------------------------------------------------------------------ #
    def tick(self) -> dict:
        """One engine step: admit waiting requests + decode every lane.

        Five host spans cover the tick end to end, one after another, in
        the profiler's trace (on the device planes' clock when a profiler
        session runs; about a microsecond each when none does):
        ``loop.control``, ``loop.admission`` (metadata ``rows`` taken into
        the batch, ``batch`` its width), ``loop.dispatch`` (the
        ``serve_step`` call), ``loop.readback`` (one blocking device→host
        read of the step's packed ``wire``; metadata ``reads``, the reads
        issued, and ``bytes``, their size) and ``loop.bookkeeping``
        (metadata ``held``: rows taken but not serviced, re-queued or
        dropped).  The return carries the same ``taken`` and ``held``
        counts."""
        with TraceAnnotation("loop.control"):
            if self.cp is not None:
                self.cp.heartbeat(self)          # liveness lease
            elif self.remote is not None:        # transport-attached: plans
                self.remote.pump(self.ticks)     # in, heartbeat + load out
            # injected faults roll progress back BEFORE the step, so a held
            # slot can't complete this tick
            if self.fault is not None:
                pool = self.fault.apply(self.state.pool, self.ticks)
                if pool is not self.state.pool:
                    self.state = self.state._replace(pool=pool)
        with TraceAnnotation("loop.admission") as span:
            self._release_matured()
            reqs, taken = self._next_admission()
            span.set_metadata(rows=len(taken), batch=self.admit_batch)
        with TraceAnnotation("loop.dispatch"):
            self.state, out = self.serve_step(self.params, self.state, reqs)
        with TraceAnnotation("loop.readback") as span:
            wire = np.asarray(out["wire"])       # the tick's one read
            span.set_metadata(reads=1, bytes=wire.nbytes)
            I, C = self.balancer.n_instances, self.balancer.slots
            # views of the packed vector; ids: those serviced this tick
            emitted, done, ids = wire[:-1].reshape(3, I, C)
            active = int(wire[-1])
        with TraceAnnotation("loop.bookkeeping") as span:
            serviced = set()
            for i in range(I):
                for s in range(C):
                    rid = int(ids[i, s])
                    if rid >= 0 and rid in self.inflight:
                        serviced.add(rid)
                        req = self.inflight[rid]
                        req.tokens.append(int(emitted[i, s]))
                        if done[i, s]:
                            r = self.inflight.pop(rid)
                            r.t_done = time.perf_counter()
                            self.done.append(r)
            # held requests (pool exhausted / unroutable this tick)
            # re-queue — the paper's bounded hold queue lives on the host
            # ingress
            held = 0
            for r in taken:
                if r.req_id not in serviced and r.req_id in self.inflight:
                    self.inflight.pop(r.req_id)
                    held += 1
                    if r.retries == 0:       # first hold: count the REQUEST
                        self.held_first += 1  # (attempts land in overflow)
                    r.retries += 1
                    self._backoff(r)         # park (or drop at max_retries);
                    #                          submitted == done + dropped +
                    #                          n_queued + inflight throughout
            span.set_metadata(held=held)
            self.ticks += 1
            if sanitize_enabled():
                assert_host("loop", dict(
                    submitted=self.submitted, done=len(self.done),
                    dropped=len(self.dropped), queued=self.n_queued,
                    inflight=len(self.inflight)))
        return {"active": active, "queued": self.n_queued,
                "done": len(self.done), "dropped": len(self.dropped),
                "taken": len(taken), "held": held}

    def drain(self, max_ticks: int = 10_000) -> DrainReport:
        """Tick until idle (or the budget runs out) and report everything —
        a drain that strands queued/inflight work says so instead of
        silently returning only the completions."""
        t = 0
        while (self.queue or self._waiting or self.inflight) \
                and t < max_ticks:
            self.tick()
            t += 1
        return DrainReport(done=self.done, dropped=self.dropped,
                           queued=self.n_queued,
                           inflight=len(self.inflight),
                           held_first=self.held_first)
