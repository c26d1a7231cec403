"""The fused completion kernel: per pool slot a handful of operations for
done detection, release and the counters, per endpoint the two EWMA
updates; bytes of the pool, the decode's next tokens, the load and rx
counters, both EWMAs and its results."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.costs import nbytes, shapes

COMPLETE_SLOT_OPS = 6        # done test (2), length, token, release, rx
EWMA_OPS = 6                 # two (sub, mul, add) updates per endpoint


def cost(state, params, app: dict, admit_batch: int,
         fold: str | None = None) -> tuple[float, float]:
    from repro.kernels import ops
    from repro.kernels.tune import plan_complete

    routing, pool = shapes(state.routing), shapes(state.pool)
    rx = shapes(state.metrics.rx_bytes)
    nxt = jax.ShapeDtypeStruct(pool.req_id.shape, jnp.int32)
    block_i, fold_c = plan_complete(pool.req_id.shape, fold=fold, block_i=8)
    # shapes only: the length budget does not change them
    result = jax.eval_shape(lambda *a: ops.complete(
        *a, eos=1, max_len=16, block_i=block_i, fold=fold_c),
        pool, nxt, routing.ep_load, rx, routing.ep_inflight_ewma,
        routing.ep_tput_ewma)
    ops_ = math.prod(pool.req_id.shape) * COMPLETE_SLOT_OPS \
        + routing.ep_load.shape[0] * EWMA_OPS
    moved = nbytes((pool, nxt, routing.ep_load, rx,
                    routing.ep_inflight_ewma, routing.ep_tput_ewma, result))
    return float(ops_), float(moved)
