"""XLB socket relay — in-graph payload redirection between "sockets".

Paper mapping (§4.1): a p-sock relays a message straight into the TX queue of
the chosen i-sock; responses come back i-sock.RX → p-sock.RX.  On a TPU mesh
the analogous primitive is *capacity-bounded counting-sort dispatch*: payload
rows move to their destination's buffer slot in one scatter (single-device) or
one all-to-all hop over the ICI (expert/instance parallel) — never through the
host.

Three interchangeable dispatch methods (tests cross-check them):
  * ``sort``    — counting-sort positions + scatter; O(N log N) compare, O(N·D)
                  data movement.  Default.
  * ``cumsum``  — one-hot cumsum positions (GShard-style rank); O(N·E) but
                  matmul-friendly; the Pallas ``relay_dispatch`` kernel tiles
                  this form.
  * ``einsum``  — full dense one-hot dispatch/combine einsum (GShard).  The
                  oracle: simplest semantics, highest FLOPs.

The ``a2a`` path (``sharded_relay``) wraps dispatch in ``shard_map`` so the
relay hop is an explicit ``all_to_all`` over a named mesh axis — the
collective schedule the roofline analysis attributes to the technique.

Overflow (connection-pool exhaustion, paper's held requests) is counted and
surfaced in metrics as ``overflow_frac``; overflowing rows are dropped by the
dispatch and restored by the residual connection of the caller (MoE) or held
by the serving engine (router).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class RelayMeta(NamedTuple):
    """Bookkeeping produced by dispatch, consumed by combine."""

    idx: jax.Array        # (N,) int32 destination id per payload row
    slot: jax.Array       # (N,) int32 slot within the destination pool
    ok: jax.Array         # (N,) bool  row fit inside capacity (per-SOURCE
    #                       quota under sharded_apply — see its docstring)
    load: jax.Array       # (E,) int32 rows destined per backend, pre-drop
    #                       (GLOBAL — psum'd over the axis — when produced
    #                       by sharded_apply; local rows otherwise)
    overflow_frac: jax.Array  # () fraction of rows dropped


# --------------------------------------------------------------------------- #
# Slot assignment ("which position in the destination's connection pool")
# --------------------------------------------------------------------------- #


def positions_sort(idx: jax.Array, n_dest: int) -> tuple[jax.Array, jax.Array]:
    """Counting-sort rank: stable position of each row within its destination.

    Returns (slot (N,), load (E,)).
    """
    N = idx.shape[0]
    order = jnp.argsort(idx, stable=True)                     # (N,)
    sorted_idx = idx[order]
    load = jnp.bincount(idx, length=n_dest)                   # (E,)
    starts = jnp.cumsum(load) - load                          # (E,)
    # shard_admit steers dropped rows to the sentinel destination
    # ``n_dest``: their rank is never consumed, but the gather must still
    # stay inside ``starts`` — OOB reads are undefined once compiled
    pos_sorted = jnp.arange(N, dtype=jnp.int32) \
        - starts[jnp.minimum(sorted_idx, n_dest - 1)]
    slot = jnp.zeros((N,), jnp.int32).at[order].set(
        pos_sorted.astype(jnp.int32), mode="drop")
    return slot, load.astype(jnp.int32)


def positions_cumsum(idx: jax.Array, n_dest: int) -> tuple[jax.Array, jax.Array]:
    """One-hot cumsum rank (GShard form).  O(N·E) memory."""
    oh = jax.nn.one_hot(idx, n_dest, dtype=jnp.int32)         # (N,E)
    ranks = jnp.cumsum(oh, axis=0) - oh                       # rank before self
    slot = jnp.sum(ranks * oh, axis=-1).astype(jnp.int32)
    load = jnp.sum(oh, axis=0).astype(jnp.int32)
    return slot, load


_POSITIONS = {"sort": positions_sort, "cumsum": positions_cumsum}


# --------------------------------------------------------------------------- #
# Dispatch / combine (single-shard)
# --------------------------------------------------------------------------- #


def relay_dispatch(x: jax.Array, idx: jax.Array, n_dest: int, capacity: int,
                   method: str = "sort") -> tuple[jax.Array, RelayMeta]:
    """Scatter payload rows x:(N,D) into per-destination pools (E,C,D).

    Rows beyond ``capacity`` land in a dump slot and are dropped (ok=False).
    """
    N, D = x.shape
    slot, load = _POSITIONS[method](idx, n_dest)
    ok = slot < capacity
    write_slot = jnp.where(ok, slot, capacity)                # dump row = C
    buf = jnp.zeros((n_dest, capacity + 1, D), x.dtype)
    buf = buf.at[idx, write_slot].set(x, mode="drop")
    overflow = 1.0 - jnp.mean(ok.astype(jnp.float32))
    return buf[:, :capacity], RelayMeta(idx, slot, ok, load, overflow)


def relay_combine(buf: jax.Array, meta: RelayMeta, weights: jax.Array | None = None
                  ) -> jax.Array:
    """Gather rows back from pools (E,C,D) to payload order (N,D).

    ``weights``: optional (N,) scale (MoE gate weight / response weighting).
    Dropped rows come back as zeros (caller's residual covers them).
    """
    safe_slot = jnp.minimum(meta.slot, buf.shape[1] - 1)
    rows = buf[meta.idx, safe_slot]                           # (N,D)
    rows = jnp.where(meta.ok[:, None], rows, 0)
    if weights is not None:
        rows = rows * weights[:, None].astype(rows.dtype)
    return rows


# --------------------------------------------------------------------------- #
# Dense-einsum oracle (GShard): slowest, simplest
# --------------------------------------------------------------------------- #


def relay_dispatch_einsum(x, idx, n_dest: int, capacity: int):
    N, D = x.shape
    slot, load = positions_cumsum(idx, n_dest)
    ok = slot < capacity
    e_oh = jax.nn.one_hot(idx, n_dest, dtype=x.dtype)          # (N,E)
    c_oh = jax.nn.one_hot(jnp.minimum(slot, capacity - 1), capacity,
                          dtype=x.dtype)                       # (N,C)
    d_onehot = (e_oh[:, :, None] * c_oh[:, None, :]
                * ok[:, None, None].astype(x.dtype))           # (N,E,C)
    buf = jnp.einsum("nec,nd->ecd", d_onehot, x)
    overflow = 1.0 - jnp.mean(ok.astype(jnp.float32))
    return buf, RelayMeta(idx, slot, ok, load, overflow), d_onehot


def relay_combine_einsum(buf, d_onehot, weights=None):
    out = jnp.einsum("nec,ecd->nd", d_onehot.astype(buf.dtype), buf)
    if weights is not None:
        out = out * weights[:, None].astype(out.dtype)
    return out


# --------------------------------------------------------------------------- #
# Expert/instance-parallel relay: explicit all-to-all over a mesh axis
# --------------------------------------------------------------------------- #


def sharded_apply(x, idx, weights, n_dest: int, capacity: int, axis: str,
                  backend_fn, backend_params):
    """shard_map body: relay local rows over ``axis`` to backend owners,
    apply ``backend_fn(params_local, pool)`` on each owner, relay back.

    Must run inside ``shard_map`` with mesh axis ``axis`` of size M;
    ``n_dest % M == 0``; backend b lives on shard b // (n_dest // M).
    x: (N_loc, D) local rows; idx: (N_loc,) global destination ids.
    Returns (out (N_loc,D), meta).

    Meta semantics across the shards (pinned by the 4-shard round-trip test
    in tests/test_shard_admit.py): ``ok``/``slot``/``overflow_frac`` are
    **per-source** — each source shard owns ``capacity`` slots at every
    destination, so a row is dropped against its own shard's quota (a
    destination absorbs up to ``M * capacity`` rows in total) and
    ``overflow_frac`` is the axis-mean of the per-source drop fractions;
    ``load`` is the **global pre-drop** row count per destination
    (psum'd over ``axis``), matching the single-shard dispatch on the
    concatenated rows.
    """
    M = jax.lax.axis_size(axis)
    E_loc = n_dest // M
    # local dispatch into per-destination pools with per-source capacity
    buf, meta = relay_dispatch(x, idx, n_dest, capacity)       # (E, C, D)
    # relay hop: all_to_all moves each destination pool to its owner shard.
    # (M, E_loc, C, D) --a2a--> (M, E_loc, C, D) where leading axis becomes
    # the source-shard axis on the receiving side.
    buf = buf.reshape(M, E_loc, capacity, -1)
    buf = jax.lax.all_to_all(buf, axis, split_axis=0, concat_axis=0, tiled=False)
    # owner now holds (M, E_loc, C, D): pools from every source shard
    pool = buf.transpose(1, 0, 2, 3).reshape(E_loc, M * capacity, -1)
    out_pool = backend_fn(backend_params, pool)                # (E_loc, M*C, D')
    # reverse relay
    out_pool = out_pool.reshape(E_loc, M, capacity, -1).transpose(1, 0, 2, 3)
    out_pool = jax.lax.all_to_all(out_pool, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
    out_buf = out_pool.reshape(n_dest, capacity, -1)
    meta = meta._replace(
        load=jax.lax.psum(meta.load, axis),
        overflow_frac=jax.lax.pmean(meta.overflow_frac, axis))
    return relay_combine(out_buf, meta, weights), meta
