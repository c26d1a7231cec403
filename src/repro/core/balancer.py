"""The unified datapath seam: one ``Balancer`` protocol, three architectures.

The paper's comparison (Fig. 1) pits three placements of the L7 balancer —
per-instance sidecar proxy (Istio), shared global proxy (Cilium), and the
in-kernel interposition of XLB — against one another over the *same*
service contract.  This module pins that contract as a structural protocol
so every driver (``ServeLoop``, ``launch/serve.py``, ``benchmarks``) is
written once against the seam and never against an engine:

  * ``init_state(routing)``     → opaque engine state (pools, caches, ...)
  * ``admit(state, reqs)``      → state with the batch routed + committed
  * ``step(params, state)``     → (state, out) one decode + completion tick
  * ``make_jitted()``           → fused ``serve_step(params, state, reqs)``
  * ``get_routing(state)``      → the live ``RoutingState`` the engine reads
  * ``apply_refresh(state, plan)`` → state after a control-plane transaction
                                  (config swap + endpoint-reference remap)

``step``/``serve_step`` return an ``out`` dict with the same keys for every
engine: ``emitted``/``done``/``req_id`` as (I, C) arrays over the connection
pool and an ``active`` count — the host driver never branches on the mode.
``wire`` packs all four into one int32 vector of length ``3·I·C + 1``:
``emitted``, ``done`` (0/1) and ``req_id``, each raveled row-major over
(I, C), then ``active``.  ``ServeLoop`` reads only ``wire``, so a tick
costs one device→host read; ``I`` and ``C`` are the engine's
``n_instances`` and ``slots``.

The shared wire types live here too: ``RequestBatch`` (host-ingress output)
and ``PoolState`` (per-(instance, slot) connection state).  They are plain
NamedTuples, so the XLB engine holds device arrays in them while the sidecar
baselines hold host numpy arrays — same shape contract, different residency,
exactly the architectural difference the paper measures.
"""

from __future__ import annotations

from typing import Any, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
from typing import NamedTuple


class RequestBatch(NamedTuple):
    """Host-ingress output: fixed-size admission batch (pad with req_id=-1)."""

    req_id: jax.Array     # (R,) int32, -1 = padding
    svc: jax.Array        # (R,) int32 virtual-IP/service id
    features: jax.Array   # (R, N_FEATURES) int32 hashed L7 fields
    token: jax.Array      # (R,) int32 first prompt token
    msg_bytes: jax.Array  # (R,) int32 payload size (traffic metrics)


class PoolState(NamedTuple):
    """Per-(instance, slot) live-connection state."""

    req_id: jax.Array      # (I, C) int32, -1 = free
    endpoint: jax.Array    # (I, C) int32 (for load release)
    svc: jax.Array         # (I, C) int32
    length: jax.Array      # (I, C) int32
    token: jax.Array       # (I, C) int32 last emitted/fed token
    active: jax.Array      # (I, C) bool

    @staticmethod
    def init(I: int, C: int) -> "PoolState":
        return PoolState(
            req_id=jnp.full((I, C), -1, jnp.int32),
            endpoint=jnp.full((I, C), -1, jnp.int32),
            svc=jnp.zeros((I, C), jnp.int32),
            length=jnp.zeros((I, C), jnp.int32),
            token=jnp.zeros((I, C), jnp.int32),
            active=jnp.zeros((I, C), bool),
        )


@runtime_checkable
class Balancer(Protocol):
    """Structural type every serving engine implements (XLB/Istio/Cilium)."""

    n_instances: int      # I of the (I, C) pool
    slots: int            # C

    def init_state(self, routing, dtype=None) -> Any:
        """Build the engine state for one fleet around a routing snapshot."""
        ...

    def admit(self, state, reqs: RequestBatch) -> Any:
        """Route + balance + commit one admission batch into the pools."""
        ...

    def step(self, params, state) -> tuple[Any, dict]:
        """One decode step for every lane + completion handling."""
        ...

    def make_jitted(self, donate: bool = True):
        """Fused ``serve_step(params, state, reqs) -> (state, out)``."""
        ...

    def get_routing(self, state):
        """The live RoutingState this engine's datapath reads."""
        ...

    def apply_refresh(self, state, plan) -> Any:
        """Apply a ControlPlane ``RefreshPlan``: swap the config tables,
        migrate load counters, and remap pool endpoint references."""
        ...


ENGINE_KINDS = ("xlb", "istio", "cilium")


def make_balancer(kind: str, cfg, n_instances: int, slots: int,
                  max_len: int, **kw) -> Balancer:
    """Factory over the three architectures — the only place a driver ever
    names an engine class."""
    if kind == "xlb":
        from repro.core.interpose import Engine
        return Engine(cfg, n_instances, slots, max_len, **kw)
    if kind == "istio":
        from repro.core.sidecar import IstioEngine
        return IstioEngine(cfg, n_instances, slots, max_len, **kw)
    if kind == "cilium":
        from repro.core.sidecar import CiliumEngine
        return CiliumEngine(cfg, n_instances, slots, max_len, **kw)
    raise ValueError(f"unknown engine kind {kind!r}; "
                     f"choose from {ENGINE_KINDS}")
