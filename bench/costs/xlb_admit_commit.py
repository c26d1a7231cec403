"""The fused admission kernel: per admitted row one compare per feature
column of the rule walk, per lane of its cluster window and per slot of
its instance row; bytes of the request batch, the routing tables it
reads, the pool, the batch's draws and its results."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.costs import nbytes, shapes
from bench.reference import WINDOW

# Routing tables the kernel reads (its table operands).
ADMIT_READS = ("svc_rule_start", "svc_rule_count", "rule_field",
               "rule_value", "rule_cluster", "cluster_ep_start",
               "cluster_ep_count", "cluster_policy", "ep_instance",
               "ep_weight", "ep_drained", "maglev_table", "ep_load",
               "rr_cursor", "aff_key", "aff_ep")


def cost(state, params, app: dict, admit_batch: int,
         fold: str | None = None) -> tuple[float, float]:
    from repro.core.balancer import RequestBatch
    from repro.core.routing_table import N_FEATURES
    from repro.kernels import ops
    from repro.kernels.tune import plan_admit

    R = admit_batch
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    reqs = RequestBatch(i32(R), i32(R), i32(R, N_FEATURES), i32(R), i32(R))
    routing, pool = shapes(state.routing), shapes(state.pool)
    rnd, gum = i32(R), jax.ShapeDtypeStruct((R, WINDOW), jnp.float32)
    block_r, fold_a = plan_admit(R, pool.req_id.shape, fold=fold,
                                 commit=True, block_r=min(R, 256))
    result = jax.eval_shape(lambda *a: ops.admit_commit(
        *a, block_r=block_r, fold=fold_a), reqs, routing, pool, rnd, gum)
    F = reqs.features.shape[1]
    C = pool.req_id.shape[1]
    ops_ = R * (F + WINDOW + C)
    tables = routing._asdict()
    moved = nbytes((reqs, [tables[k] for k in ADMIT_READS], pool, rnd, gum,
                    result))
    return float(ops_), float(moved)
