"""Operations and bytes of one tick's work, counted from shapes.

Bytes are every operand and every result of a kernel, each once: what the
kernel must at least move between HBM and the core, whichever fold
implements it.  Operations are what the algorithm needs, not what a fold
spends: per admitted row one compare per feature column of the rule walk,
per lane of its cluster window and per slot of its instance row; per pool
slot a handful for done detection, release and the counters; per
endpoint the two EWMA updates.  The decode's operations are its matmul and
attention FLOPs over every pool slot, the attention over the whole cache
length as the step computes it.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import WINDOW

# Routing tables the admit-commit kernel reads (its table operands).
ADMIT_READS = ("svc_rule_start", "svc_rule_count", "rule_field",
               "rule_value", "rule_cluster", "cluster_ep_start",
               "cluster_ep_count", "cluster_policy", "ep_instance",
               "ep_weight", "ep_drained", "maglev_table", "ep_load",
               "rr_cursor", "aff_key", "aff_ep")
COMPLETE_SLOT_OPS = 6        # done test (2), length, token, release, rx
EWMA_OPS = 6                 # two (sub, mul, add) updates per endpoint


def nbytes(tree) -> int:
    return sum(math.prod(x.shape) * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def admit_commit_cost(reqs, tables: dict, pool, rnd, gumbel, result
                      ) -> tuple[float, float]:
    """(ops, bytes) of one admit-commit launch."""
    R, F = reqs.features.shape
    I, C = pool.req_id.shape
    W = gumbel.shape[1]
    ops = R * (F + W + C)
    moved = nbytes((reqs, [tables[k] for k in ADMIT_READS], pool, rnd,
                    gumbel, result))
    return float(ops), float(moved)


def complete_cost(pool, nxt, ep_load, rx_bytes, ewl, ewt, result
                  ) -> tuple[float, float]:
    """(ops, bytes) of one completion launch."""
    ops = math.prod(pool.req_id.shape) * COMPLETE_SLOT_OPS \
        + ep_load.shape[0] * EWMA_OPS
    moved = nbytes((pool, nxt, ep_load, rx_bytes, ewl, ewt, result))
    return float(ops), float(moved)


def decode_flops(app: dict, batch: int, cache_len: int) -> float:
    """FLOPs of one decode step over ``batch`` slots."""
    D, H, K, hd = app["d_model"], app["n_heads"], app["n_kv_heads"], \
        app["head_dim"]
    Fd, L = app["d_ff"], app["n_layers"]
    vocab = -(-app["vocab"] // 256) * 256
    ffn = (3 if app["ffn_act"] == "swiglu" else 2) * D * Fd
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + ffn
    matmul = 2 * batch * (L * per_layer + D * vocab)
    attn = 4 * batch * L * H * hd * cache_len
    return float(matmul + attn)


def kernel_shapes(state, admit_batch: int, fold: str | None = None):
    """Operand and result shapes of both datapath kernels at the live
    state's sizes, the results from the program's own wrappers traced
    abstractly (``fold`` None: the backend's)."""
    from repro.core.balancer import RequestBatch
    from repro.core.routing_table import N_FEATURES
    from repro.kernels import ops
    from repro.kernels.tune import plan_admit, plan_complete

    S = jax.ShapeDtypeStruct
    sd = lambda x: S(x.shape, x.dtype)
    R = admit_batch
    i32 = lambda *s: S(s, jnp.int32)
    reqs = RequestBatch(i32(R), i32(R), i32(R, N_FEATURES), i32(R), i32(R))
    routing = jax.tree.map(sd, state.routing)
    pool = jax.tree.map(sd, state.pool)
    rnd, gum = i32(R), S((R, WINDOW), jnp.float32)
    block_r, fold_a = plan_admit(R, pool.req_id.shape, fold=fold,
                                 commit=True, block_r=min(R, 256))
    adm = jax.eval_shape(lambda *a: ops.admit_commit(
        *a, block_r=block_r, fold=fold_a), reqs, routing, pool, rnd, gum)
    nxt = i32(*pool.req_id.shape)
    block_i, fold_c = plan_complete(pool.req_id.shape, fold=fold,
                                    block_i=8)
    # shapes only: the length budget does not change them
    cmp_ = jax.eval_shape(lambda *a: ops.complete(
        *a, eos=1, max_len=16, block_i=block_i, fold=fold_c),
        pool, nxt, routing.ep_load, state.metrics.rx_bytes,
        routing.ep_inflight_ewma, routing.ep_tput_ewma)
    return {
        "xlb_admit_commit": admit_commit_cost(
            reqs, routing._asdict(), pool, rnd, gum, adm),
        "xlb_complete": complete_cost(
            pool, nxt, routing.ep_load, state.metrics.rx_bytes,
            routing.ep_inflight_ewma, routing.ep_tput_ewma, cmp_),
    }
