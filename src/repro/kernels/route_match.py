"""XLB datapath hot loop as fused Pallas kernels (the paper's filter_manager
→ route_manager → load_balancer tail-call chain, Figure 4).

Two entry points:

``route_match``
  rule match + least-request endpoint scan only (the original kernel, kept
  as the small building block and for the kernel test sweeps).

``admit``
  the full in-kernel admission datapath: rule match → per-cluster policy
  dispatch (round-robin / random / least-request / weighted) → endpoint
  selection with *sequentially consistent* load counters → free-slot
  allocation → fused per-service metrics.  The mutable LB state (``ep_load``,
  ``rr_cursor``, per-instance slot cursors) is carried in VMEM scratch across
  the sequential grid — the same running-counter trick as
  ``kernels/relay_dispatch.py`` — so a request admitted in tile ``i`` is
  visible to every decision in tile ``i+1``, exactly like the eBPF map a
  per-packet program updates in place.

``admit_commit``
  ``admit`` plus the pool-commit stage: admitted requests write all six
  per-(instance, slot) connection-state fields (req_id, endpoint, svc,
  length, token, active) directly inside the kernel, so ``Engine.admit``
  needs no post-pass scatters at all — the whole connect path is one Pallas
  program.  The (I, C) pool rides in the revisited whole-array output
  blocks; each tile folds its writes in with a one-hot mask (slots are
  collision-free by construction: the slot allocator hands out each free
  slot at most once per batch).

Sequential least-request without a per-request scan: request ``r`` with
in-tile cluster rank ``ρ`` takes the endpoint owning the ``ρ``-th smallest
"ticket" of the multiset ``{load_j + t : t ≥ 0}`` ordered by (value, j) —
the water-filling closed form of "argmin then increment".  The onehot fold
finds the level by a static-depth binary search (Mosaic-friendly); the
segment fold reads it from per-cluster sorted-prefix tables (one (CL, WE)
sort shared by every request of the tile).

Every table read and aggregation (LB counters, rr cursors, slot ranks,
metrics, pool commit) goes through the fold seam at the top of this module
(DESIGN.md §5): ``fold="onehot"`` is the form Mosaic compiles for the TPU
(one-hot reductions, lane barrel shifts, triangular-matmul counts),
``fold="segment"`` gathers, scatter-adds and sorts in O(rows + buckets)
per tile — the CPU-interpreter default.

Selection consults the control plane's ``ep_drained`` mask under EVERY
policy: drained endpoints leave the eligible set at once (rr/random cycle
over the k-th *eligible* endpoint, least-request sees their load as BIG,
weighted masks their Gumbel score), and a fully-drained cluster is
unroutable like an empty one.

Grid: (R / BR,) sequential.  Tables are small (≤ 512 int32) and stay
VMEM-resident across the whole grid — the eBPF maps pinned in kernel memory.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import policy_defs
from repro.core.policy_defs import BIG  # noqa: F401  (re-export: the
# sentinel and the policy enum live in core/policy_defs.py — ONE
# definition site for kernel, oracle and staged chain, DESIGN.md §9)
from repro.core.routing_table import (MAX_EPS_PER_CLUSTER, MAX_RULES_PER_SVC,
                                      POLICY_AFFINITY, POLICY_RR, WILDCARD)
from repro.kernels.backend import resolve_fold, resolve_interpret, tile_ok


def _row_spec(block_r: int) -> pl.BlockSpec:
    """Per-request (1, R) arrays in (1, block_r) tiles: row 0 of the block
    is the tile's requests.  A 1-D (R,) operand cannot be cut into tiles
    shorter than its compiled layout's (up to 1024 lanes); a (1, R) one can
    at any multiple of 128."""
    return pl.BlockSpec((1, block_r), lambda r: (0, r))


def _table_spec(shape: tuple) -> pl.BlockSpec:
    """Whole-array BlockSpec for a VMEM-resident table: every grid step maps
    block (0, ..., 0) with rank matching the table (a closure per table, so a
    2-D table can never silently bind a 1-D index map)."""

    def index_map(r):
        return (0,) * len(shape)

    return pl.BlockSpec(shape, index_map)


# --------------------------------------------------------------------------- #
# Tiled segment folds — the aggregation strategy seam (DESIGN.md §5)
#
# Every table read and every aggregation in the datapath kernels goes
# through the helpers below (LB load counters, rr cursors, per-service
# metrics, pool commit, rank sorts, window reads).  Two implementations
# share one contract, selected by the static ``fold`` argument:
#
#   fold="onehot"   the form Mosaic compiles for the TPU.  A read of a small
#                   1-D table (at most 512 wide) is a one-hot compare/select
#                   lane reduction; a per-request window of a table is a
#                   lane barrel shift (one static ``pltpu.roll`` per index
#                   bit); a running count is a 0/1 bf16 triangular matmul
#                   with f32 accumulation (exact below 2^24); a row read of
#                   a 2-D table and the pool-cell writes are one-hot matmuls
#                   over the four 8-bit planes of the int32 values (each
#                   output cell has at most one nonzero term, so every plane
#                   is exact).  No gather, scatter, sort, cumsum or 2-D → 1-D
#                   reshape.  O(rows·buckets) VPU/MXU work per tile.
#   fold="segment"  plain gathers, scatter-adds and one stable sort —
#                   O(rows + buckets) per tile, the form XLA:CPU executes in
#                   linear time under the Pallas interpreter.  It is also the
#                   layout that psums cleanly for the mesh-sharded admission
#                   plan (per-shard (E,) partials, no dispatch matrices).
#
# Rows a caller wants dropped are steered to bucket id == width: the one-hot
# comparison never matches it, the scatter drops it via mode="drop".
# --------------------------------------------------------------------------- #


def _iota(shape: tuple, dim: int):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _take(table, ids, *, fold: str):
    """``table[ids]`` for a 1-D table and 1-D in-range ids (callers clamp)."""
    if fold == "segment":
        return table[ids]
    oh = ids[:, None] == _iota((ids.shape[0], table.shape[0]), 1)
    return jnp.sum(jnp.where(oh, table[None, :], 0).astype(table.dtype),
                   axis=1)


def _window(table, start, width: int, *, fold: str):
    """(rows, width) windows ``table[clip(start + w, 0, K - 1)]`` of a 1-D
    (K,) table, one per row, for per-row starts in [0, K)."""
    K = table.shape[0]
    idx = start[:, None] + _iota((start.shape[0], width), 1)
    if fold == "segment":
        return table[jnp.clip(idx, 0, K - 1)]
    # barrel shifter: rotate every row left by its own start, one static
    # lane roll per bit of the start
    row = table[None, :]
    x = jnp.broadcast_to(row, (start.shape[0], K))
    for b in range(max(K - 1, 1).bit_length()):
        x = jnp.where((start[:, None] >> b) & 1 == 1,
                      pltpu.roll(x, K - (1 << b), 1), x)
    last = jnp.sum(jnp.where(_iota((1, K), 1) == K - 1, row, 0),
                   axis=1, keepdims=True).astype(table.dtype)
    return jnp.where(idx > K - 1, last, x[:, :width])


def _take_cols(x, cols, *, fold: str):
    """``x[r, cols[r, w]]``: per-row column reads of a narrow (rows, F)
    array at in-range (rows, W) column ids."""
    if fold == "segment":
        return jnp.take_along_axis(x, cols, axis=1)
    out = jnp.zeros(cols.shape, x.dtype)
    for f in range(x.shape[1]):
        out = jnp.where(cols == f, x[:, f:f + 1], out)
    return out


def _first_true(mask):
    """Lane index of the first True of each row, 0 when none — ``argmax``
    of a bool mask in the min-reduce form Mosaic compiles."""
    W = mask.shape[1]
    return jnp.min(jnp.where(mask, _iota(mask.shape, 1), W), axis=1) % W


def _prefix_count(mask, axis: int, *, fold: str):
    """Inclusive running count of a 2-D bool mask along ``axis``."""
    if fold == "segment":
        return jnp.cumsum(mask.astype(jnp.int32), axis=axis)
    n = mask.shape[axis]
    m = mask.astype(jnp.bfloat16)
    if axis == 1:                                  # U[k, j] = k <= j
        tri = (_iota((n, n), 0) <= _iota((n, n), 1)).astype(jnp.bfloat16)
        out = jnp.dot(m, tri, preferred_element_type=jnp.float32)
    else:                                          # L[i, k] = k <= i
        tri = (_iota((n, n), 1) <= _iota((n, n), 0)).astype(jnp.bfloat16)
        out = jnp.dot(tri, m, preferred_element_type=jnp.float32)
    return out.astype(jnp.int32)


def _onehot_matmul(oh, vals):
    """``Σ_k oh[m, k] · vals[k, n]`` for a bool (M, K) ``oh`` and int32
    (K, N) ``vals`` where every output cell has at most one nonzero term:
    one exact bf16 matmul per 8-bit plane of the int32 bit pattern."""
    a = oh.astype(jnp.bfloat16)
    out = jnp.zeros((oh.shape[0], vals.shape[1]), jnp.int32)
    for k in range(4):
        plane = jax.lax.shift_right_logical(vals, 8 * k) & 0xFF
        p = jnp.dot(a, plane.astype(jnp.bfloat16),
                    preferred_element_type=jnp.float32)
        out = out | (p.astype(jnp.int32) << (8 * k))
    return out


def _take_rows(table, ids, *, fold: str):
    """``table[ids]``: (rows, N) rows of a 2-D int32 table at in-range ids."""
    if fold == "segment":
        return table[ids]
    return _onehot_matmul(
        ids[:, None] == _iota((ids.shape[0], table.shape[0]), 1), table)


def _take2(table, rows, cols, *, fold: str):
    """``table[rows, cols]`` of a 2-D int32 table at in-range 1-D ids."""
    if fold == "segment":
        return table[rows, cols]
    r = _take_rows(table, rows, fold=fold)
    return jnp.sum(jnp.where(cols[:, None] == _iota(r.shape, 1), r, 0),
                   axis=1)


def _seg_sum(vec, ids, vals, *, fold: str):
    """Fold ``vals`` into ``vec`` (K,) at buckets ``ids`` (same shape as
    ``vals``, 1-D or 2-D); ids >= K are dropped.  Returns the updated
    vector."""
    if fold == "segment":
        return vec.at[ids].add(vals, mode="drop")
    if ids.ndim == 2:                      # one lane column at a time
        for c in range(ids.shape[1]):
            vec = _seg_sum(vec, ids[:, c], vals[:, c], fold=fold)
        return vec
    K = vec.shape[0]
    oh = ids[:, None] == _iota((ids.shape[0], K), 1)
    return vec + jnp.sum(jnp.where(oh, vals[:, None], 0), axis=0)


def _seg_rank(ids, mask, n_seg: int, *, fold: str, block_r: int):
    """In-tile arrival rank of each row among rows sharing its id (the
    counting sort of relay_dispatch), plus the per-id row counts.  Rows
    with mask=False get an arbitrary rank and count nothing — callers gate
    on the mask.  fold="onehot": (BR, K) one-hot with an exclusive
    triangular-matmul count; fold="segment": one stable argsort + a
    segmented iota, with the counts read off the sorted keys by
    searchsorted (no scatter).  Returns (rank (BR,), counts (K,))."""
    key = jnp.where(mask, ids, n_seg)                  # masked → sentinel
    if fold == "segment":
        order = jnp.argsort(key)                       # stable: arrival order
        sk = key[order]
        iota = jax.lax.iota(jnp.int32, block_r)
        first = sk != jnp.concatenate([jnp.full((1,), -1, sk.dtype),
                                       sk[:-1]])       # segment boundaries
        start = jax.lax.cummax(jnp.where(first, iota, 0))
        rank = jnp.zeros((block_r,), jnp.int32).at[order].set(
            iota - start, mode="drop")
        edges = jnp.searchsorted(sk, jnp.arange(n_seg + 1, dtype=jnp.int32))
        return rank, (edges[1:] - edges[:-1]).astype(jnp.int32)
    oh = key[:, None] == _iota((block_r, n_seg), 1)
    before = _prefix_count(oh, 0, fold=fold) - oh.astype(jnp.int32)
    return jnp.sum(jnp.where(oh, before, 0), axis=1), \
        jnp.sum(oh.astype(jnp.int32), axis=0)


def _write_cells(inst, slot, vals, shape: tuple):
    """(I, C) image of per-row ``vals`` at cells (inst, slot), as the
    onehot fold's collision-free pool commit: rows with inst outside
    [0, I) or slot outside [0, C) write nothing."""
    I, C = shape
    R = inst.shape[0]
    lanes = inst[None, :] == _iota((I, R), 0)          # (I, R)
    cells = slot[:, None] == _iota((R, C), 1)          # (R, C)
    return _onehot_matmul(lanes, jnp.where(cells, vals[:, None], 0))


def _match_stage(svc, feats, rs_ref, rc_ref, rf_ref, rv_ref, rcl_ref, *,
                 block_r: int, fold: str):
    """Vectorised bounded rule-chain walk: first matching rule → cluster."""
    W = MAX_RULES_PER_SVC
    NR = rf_ref.shape[0]
    start = jnp.clip(_take(rs_ref[...], svc, fold=fold), 0, NR - 1)  # (BR,)
    count = _take(rc_ref[...], svc, fold=fold)
    win = _iota((block_r, W), 1)
    in_range = win < count[:, None]
    fields = jnp.clip(_window(rf_ref[...], start, W, fold=fold), 0,
                      feats.shape[1] - 1)              # (BR, W)
    expect = _window(rv_ref[...], start, W, fold=fold)
    actual = _take_cols(feats, fields, fold=fold)
    hit = in_range & ((expect == WILDCARD) | (expect == actual))
    any_hit = hit.any(axis=1)
    rix = jnp.clip(start + _first_true(hit), 0, NR - 1)
    return jnp.where(any_hit, _take(rcl_ref[...], rix, fold=fold), -1)


# --------------------------------------------------------------------------- #
# route_match: match + least-request scan (stateless building block)
# --------------------------------------------------------------------------- #


def _route_kernel(svc_ref, feat_ref, rs_ref, rc_ref, rf_ref, rv_ref,
                  rcl_ref, cs_ref, cc_ref, load_ref, cluster_ref, ep_ref, *,
                  block_r: int, fold: str):
    # clamp like _admit_kernel: a hostile/garbage svc id must not walk the
    # rule tables out of window (refs have no OOB semantics once compiled)
    svc = jnp.clip(svc_ref[0, :], 0, rs_ref.shape[0] - 1)   # (BR,)
    cluster = _match_stage(svc, feat_ref[...], rs_ref, rc_ref, rf_ref,
                           rv_ref, rcl_ref, block_r=block_r, fold=fold)
    cluster_ref[0, :] = cluster

    # least-request over the endpoint window (paper: full scan; small N)
    WE = MAX_EPS_PER_CLUSTER
    E = load_ref.shape[0]
    cl = jnp.maximum(cluster, 0)
    estart = jnp.clip(_take(cs_ref[...], cl, fold=fold), 0, E - 1)
    ecount = _take(cc_ref[...], cl, fold=fold)
    ewin = _iota((block_r, WE), 1)
    eok = ewin < ecount[:, None]
    load = jnp.where(eok, _window(load_ref[...], estart, WE, fold=fold), BIG)
    best = _first_true(load == jnp.min(load, axis=1, keepdims=True))
    ep = jnp.clip(estart + best, 0, E - 1)
    ep_ref[0, :] = jnp.where((cluster >= 0) & (ecount > 0), ep, -1)


def route_match(svc, features, state, *, block_r: int = 256,
                fold: str | None = None, interpret: bool | None = None):
    """svc: (R,) i32; features: (R, F) i32; state: RoutingState.

    Returns (cluster (R,), endpoint (R,)) — least-request selection.
    """
    R, F = features.shape
    block_r = min(block_r, R)
    assert R % block_r == 0
    grid = (R // block_r,)
    tables = [state.svc_rule_start, state.svc_rule_count, state.rule_field,
              state.rule_value, state.rule_cluster, state.cluster_ep_start,
              state.cluster_ep_count, state.ep_load]
    cluster, ep = pl.pallas_call(
        functools.partial(_route_kernel, block_r=block_r,
                          fold=resolve_fold(fold)),
        grid=grid,
        in_specs=[_row_spec(block_r),
                  pl.BlockSpec((block_r, F), lambda r: (r, 0))]
                 + [_table_spec(t.shape) for t in tables],
        out_specs=[_row_spec(block_r)] * 2,
        out_shape=[jax.ShapeDtypeStruct((1, R), jnp.int32)] * 2,
        interpret=resolve_interpret(interpret),
    )(svc[None, :], features, *tables)
    return cluster[0], ep[0]


# --------------------------------------------------------------------------- #
# admit: the fused route → balance → slot-allocate datapath
# --------------------------------------------------------------------------- #


class AdmitResult(NamedTuple):
    """Everything ``Engine.admit`` needs from one fused kernel launch."""

    cluster: jax.Array       # (R,) i32 destination cluster (-1 = no match)
    endpoint: jax.Array      # (R,) i32 global endpoint (-1 = unroutable)
    instance: jax.Array      # (R,) i32 instance lane (-1 = unroutable)
    slot: jax.Array          # (R,) i32 pool slot (-1 = held / unroutable)
    ok: jax.Array            # (R,) i32 1 = admitted into a pool slot
    ep_load: jax.Array       # (E,) i32 updated outstanding-request counters
    rr_cursor: jax.Array     # (CL,) i32 updated round-robin cursors
    svc_requests: jax.Array  # (S,) i32 admitted requests per service
    svc_tx_bytes: jax.Array  # (S,) i32 admitted payload bytes per service
    no_route: jax.Array      # () i32 valid requests with no rule match
    held: jax.Array          # () i32 routable requests without a free slot
    aff_key: jax.Array       # (AFFINITY_SLOTS,) i32 updated affinity cache
    aff_ep: jax.Array        # (AFFINITY_SLOTS,) i32


def _admit_kernel(*refs, block_r: int, commit: bool, fold: str):
    if commit:
        (rid_ref, svc_ref, feat_ref, bytes_ref, rnd_ref, gum_ref, fkey_ref,
         tok_ref,
         rs_ref, rc_ref, rf_ref, rv_ref, rcl_ref,
         cs_ref, cc_ref, cp_ref, einst_ref, ew_ref, ed_ref,
         load0_ref, cur0_ref, mg_ref, affk0_ref, affe0_ref, free_ref,
         preq0_ref, pep0_ref, psvc0_ref, plen0_ref, ptok0_ref,
         cluster_ref, ep_ref, inst_ref, slot_ref, ok_ref,
         loadout_ref, curout_ref, sreq_ref, stx_ref, cnt_ref,
         affk_ref, affe_ref,
         preq_ref, pep_ref, psvc_ref, plen_ref, ptok_ref, pact_ref,
         load_s, held_s, cur_s, icnt_s, sreq_s, stx_s, cnt_s,
         affk_s, affe_s) = refs
    else:
        (rid_ref, svc_ref, feat_ref, bytes_ref, rnd_ref, gum_ref, fkey_ref,
         rs_ref, rc_ref, rf_ref, rv_ref, rcl_ref,
         cs_ref, cc_ref, cp_ref, einst_ref, ew_ref, ed_ref,
         load0_ref, cur0_ref, mg_ref, affk0_ref, affe0_ref, free_ref,
         cluster_ref, ep_ref, inst_ref, slot_ref, ok_ref,
         loadout_ref, curout_ref, sreq_ref, stx_ref, cnt_ref,
         affk_ref, affe_ref,
         load_s, held_s, cur_s, icnt_s, sreq_s, stx_s, cnt_s,
         affk_s, affe_s) = refs
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        load_s[...] = load0_ref[...]
        held_s[...] = jnp.zeros_like(held_s)
        cur_s[...] = cur0_ref[...]
        icnt_s[...] = jnp.zeros_like(icnt_s)
        sreq_s[...] = jnp.zeros_like(sreq_s)
        stx_s[...] = jnp.zeros_like(stx_s)
        cnt_s[...] = jnp.zeros_like(cnt_s)
        # session-affinity cache rides in VMEM scratch across the grid —
        # the same carried-map trick as the load counters, so a flow
        # pinned in tile i sticks for every request of tile i+1
        affk_s[...] = affk0_ref[...]
        affe_s[...] = affe0_ref[...]
        if commit:
            # the pool rides in whole-array output blocks revisited by every
            # grid step: seed from the incoming pool, fold writes per tile
            preq_ref[...] = preq0_ref[...]
            pep_ref[...] = pep0_ref[...]
            psvc_ref[...] = psvc0_ref[...]
            plen_ref[...] = plen0_ref[...]
            ptok_ref[...] = ptok0_ref[...]
            pact_ref[...] = 1 - free_ref[...]

    S = rs_ref.shape[0]
    CL = cc_ref.shape[0]
    E = load0_ref.shape[0]
    I, C = free_ref.shape
    WE = MAX_EPS_PER_CLUSTER

    # ---- stage 1: content match (vectorised rule-chain walk) ---------- #
    valid = rid_ref[0, :] >= 0
    svc = jnp.clip(svc_ref[0, :], 0, S - 1)
    cluster = _match_stage(svc, feat_ref[...], rs_ref, rc_ref, rf_ref,
                           rv_ref, rcl_ref, block_r=block_r, fold=fold)
    cluster = jnp.where(valid, cluster, -1)

    cl = jnp.maximum(cluster, 0)
    count = _take(cc_ref[...], cl, fold=fold)          # (BR,)
    estart = jnp.clip(_take(cs_ref[...], cl, fold=fold), 0, E - 1)
    policy = _take(cp_ref[...], cl, fold=fold)

    ewin = _iota((block_r, WE), 1)
    eidx = jnp.clip(estart[:, None] + ewin, 0, E - 1)  # (BR, WE)
    eok_w = ewin < count[:, None]
    zoff = lambda: jnp.zeros((block_r,), jnp.int32)
    window = functools.partial(_window, start=estart, width=WE, fold=fold)

    # eligibility: inside the window AND not draining — the control plane's
    # datapath-visible drain mask gates selection under EVERY policy; a
    # cluster whose endpoints are all draining (or gone) is unroutable.
    # The segment fold branches at RUNTIME on "anything draining at all"
    # (an (E,) table scan): the no-drain steady state skips the per-request
    # mask gather and the k-th-eligible remap entirely — both are identity
    # then, so the branches are bit-equal.  The onehot fold stays branch-
    # free (Mosaic prefers one straight-line vector program).
    if fold == "segment":
        any_dr = jnp.any(ed_ref[...] != 0)
        eok = jax.lax.cond(any_dr,
                           lambda: eok_w & (window(ed_ref[...]) == 0),
                           lambda: eok_w)
        cnt2 = jax.lax.cond(
            any_dr, lambda: jnp.sum(eok.astype(jnp.int32), axis=1),
            lambda: jnp.clip(count, 0, WE))
    else:
        eok = eok_w & (window(ed_ref[...]) == 0)
        cnt2 = jnp.sum(eok.astype(jnp.int32), axis=1)  # eligible endpoints
    cnt1 = jnp.maximum(cnt2, 1)
    routable = valid & (cluster >= 0) & (cnt2 > 0)

    # in-tile arrival rank within each cluster (segment-fold counting sort,
    # cf. relay_dispatch) — only routable requests consume ranks; the
    # per-cluster counts ride along for the cursor fold (no extra scatter)
    rank_c, counts_c = _seg_rank(cl, routable, CL, fold=fold,
                                 block_r=block_r)

    # the onehot fold counts the eligible prefix once per tile; the segment
    # fold only inside the (rarely taken) draining branches
    cum_e = None if fold == "segment" else _prefix_count(eok, 1, fold=fold)

    def kth(k):
        """Window offset of the k-th *eligible* endpoint (== k itself when
        nothing is draining, so the pre-mask selection is unchanged)."""
        c = _prefix_count(eok, 1, fold=fold) if cum_e is None else cum_e
        return _first_true(eok & (c == (k + 1)[:, None]))

    if fold == "segment":
        # the k-th-eligible remap is skipped while nothing drains (kth is
        # the identity on modular indices then — branches are bit-equal)
        def cyc(k):
            return jax.lax.cond(any_dr, lambda: kth(k), lambda: k)
    else:
        cyc = kth

    # ---- stage 2: policy dispatch (the registry seam, DESIGN.md §9) --- #
    # every policy's selection math lives in core/policy_defs.py as ONE
    # ``kernel_offset`` hook serving both folds; this kernel only builds
    # the ctx (eligibility windows, fold helpers, carried counters) and
    # folds the per-policy window offsets through one select chain.  Under
    # the segment fold, gated policies no cluster uses are skipped at
    # runtime (the taken lax.cond branch only).
    ctx = policy_defs.KernelCtx(
        fold=fold, block_r=block_r, policy=policy, cl=cl,
        routable=routable, rank_c=rank_c, estart=estart, count=count,
        cnt1=cnt1, cnt2=cnt2, eidx=eidx, eok=eok,
        rnd=rnd_ref[0, :], fkey=fkey_ref[0, :], gum=gum_ref[...],
        loads=load_s[...], ew=ew_ref[...], ed=ed_ref[...],
        cs_vec=cs_ref[...], cc_vec=cc_ref[...],
        cur_cl=_take(cur_s[...], cl, fold=fold),
        mg_tab=mg_ref[...], aff_key=affk_s[...], aff_ep=affe_s[...],
        kth=kth, cyc=cyc, window=window,
        take=functools.partial(_take, fold=fold),
        take2=functools.partial(_take2, fold=fold),
        first_true=_first_true,
        prefix_count=functools.partial(_prefix_count, axis=1, fold=fold),
        seg_rank=functools.partial(_seg_rank, fold=fold, block_r=block_r))

    default_off = None
    conds, offs = [], []
    for p in policy_defs.REGISTRY:
        fn = (lambda p=p: p.kernel_offset(ctx).astype(jnp.int32))
        if fold == "segment" and p.gate:
            o_p = jax.lax.cond(jnp.any(cp_ref[...] == p.enum), fn, zoff)
        else:
            o_p = fn()
        if p.enum == POLICY_RR:         # rr doubles as the unknown-policy
            default_off = o_p           # fallback (oracle parity)
        else:
            conds.append(policy == p.enum)
            offs.append(o_p)

    off = default_off                   # jnp.select, first match wins
    for c_p, o_p in reversed(list(zip(conds, offs))):
        off = jnp.where(c_p, o_p, off)
    ep = jnp.where(routable, jnp.clip(estart + off, 0, E - 1), -1)
    epc = jnp.maximum(ep, 0)
    inst = jnp.where(routable, _take(einst_ref[...], epc, fold=fold), -1)
    instc = jnp.clip(inst, 0, I - 1)

    # ---- stage 3: free-slot allocation (counting-sort fold) ----------- #
    rank_i0, counts_i = _seg_rank(instc, routable, I, fold=fold,
                                  block_r=block_r)
    rank_i = _take(icnt_s[...], instc, fold=fold) + rank_i0
    if fold == "segment":
        # per-INSTANCE free-slot prefix (I·C elements, once per tile)
        # gathered per request — not a (BR, C) row cumsum
        fprefix = jnp.cumsum(free_ref[...], axis=1)    # (I, C)
        rows = free_ref[...][instc]                    # (BR, C) free=1
        prefix = fprefix[instc]
        n_free = fprefix[:, C - 1][instc]
    else:
        rows = _take_rows(free_ref[...], instc, fold=fold)
        prefix = _prefix_count(rows > 0, 1, fold=fold)
        n_free = prefix[:, C - 1]
    ok = routable & (rank_i < n_free)
    hit = (rows > 0) & (prefix == (rank_i + 1)[:, None])
    slot = jnp.where(ok, _first_true(hit), -1)
    held = routable & ~ok

    # ---- per-request outputs ------------------------------------------ #
    cluster_ref[0, :] = cluster
    ep_ref[0, :] = ep
    inst_ref[0, :] = inst
    slot_ref[0, :] = slot
    ok_ref[0, :] = ok.astype(jnp.int32)

    # ---- stage 4 (commit mode): pool writeback ------------------------ #
    if commit:
        # the slot allocator never hands the same (inst, slot) to two
        # requests in one batch, so each pool cell has at most one writer
        if fold == "segment":
            # scatter-set straight into the revisited output blocks;
            # un-admitted rows steer to an out-of-bounds lane and drop
            ii = jnp.where(ok, instc, I)
            ss = jnp.where(ok, slot, 0)

            def commit_fold(ref, vals):
                ref[...] = ref[...].at[ii, ss].set(vals, mode="drop")

            commit_fold(pact_ref, jnp.ones_like(slot))
        else:
            # one-hot matmuls over the (I, C) cells: un-admitted rows carry
            # slot -1 and match no cell
            def cells(vals):
                return _write_cells(instc, slot, vals, (I, C))

            wrote = cells(jnp.ones_like(slot)) > 0

            def commit_fold(ref, vals):
                ref[...] = jnp.where(wrote, cells(vals), ref[...])

            pact_ref[...] = jnp.where(wrote, 1, pact_ref[...])

        commit_fold(preq_ref, rid_ref[0, :])
        commit_fold(pep_ref, ep)
        commit_fold(psvc_ref, svc_ref[0, :])  # raw svc, as the engine stores it
        commit_fold(plen_ref, jnp.zeros_like(slot))
        commit_fold(ptok_ref, tok_ref[0, :])

    # ---- session-affinity cache fold (policy_defs owns the write rule:
    # first writer per slot, never evicting a live flow) — gated like the
    # other policies under the segment fold ---------------------------- #
    if fold == "segment":
        affk_new, affe_new = jax.lax.cond(
            jnp.any(cp_ref[...] == POLICY_AFFINITY),
            lambda: policy_defs.affinity_kernel_update(ctx, ep),
            lambda: (affk_s[...], affe_s[...]))
    else:
        affk_new, affe_new = policy_defs.affinity_kernel_update(ctx, ep)
    affk_s[...] = affk_new
    affe_s[...] = affe_new

    # ---- carried LB state + fused metrics (tiled segment folds) ------- #
    one = jnp.ones((block_r,), jnp.int32)
    ep_ids = jnp.where(routable, epc, E)               # masked rows drop
    load_s[...] = _seg_sum(load_s[...], ep_ids, one, fold=fold)
    held_s[...] = _seg_sum(held_s[...], jnp.where(held, epc, E), one,
                           fold=fold)
    # the cursor carries RAW counts across tiles (reduced modulo only at
    # emit): a per-tile modulo by the cluster size would make the k-th-
    # eligible offset depend on the tile boundary whenever endpoints are
    # draining (cnt2 < count), breaking block_r-independence.  Both count
    # vectors fall out of the rank sorts — no extra fold.
    cur_s[...] = cur_s[...] + counts_c
    icnt_s[...] = icnt_s[...] + counts_i
    # per-service metrics drop svc >= S (the staged scatter's mode="drop")
    # instead of folding rogue ids into service S-1 via the table clip
    svc_ids = jnp.where(ok & (svc_ref[0, :] < S), svc, S)
    sreq_s[...] = _seg_sum(sreq_s[...], svc_ids, one, fold=fold)
    stx_s[...] = _seg_sum(stx_s[...], svc_ids, bytes_ref[0, :], fold=fold)
    cnt_s[...] = cnt_s[...] + jnp.stack(
        [jnp.sum((valid & (cluster < 0)).astype(jnp.int32)),
         jnp.sum(held.astype(jnp.int32))])

    @pl.when(i == pl.num_programs(0) - 1)
    def _emit():
        # held requests release their counter (connection close of the
        # paper's hold queue) — folded into the final emit
        loadout_ref[...] = load_s[...] - held_s[...]
        curout_ref[...] = cur_s[...] % jnp.maximum(cc_ref[...], 1)
        sreq_ref[...] = sreq_s[...]
        stx_ref[...] = stx_s[...]
        cnt_ref[...] = cnt_s[...]
        affk_ref[...] = affk_s[...]
        affe_ref[...] = affe_s[...]


class AdmitCommitResult(NamedTuple):
    """``AdmitResult`` plus the committed (I, C) connection pools."""

    cluster: jax.Array
    endpoint: jax.Array
    instance: jax.Array
    slot: jax.Array
    ok: jax.Array
    ep_load: jax.Array
    rr_cursor: jax.Array
    svc_requests: jax.Array
    svc_tx_bytes: jax.Array
    no_route: jax.Array
    held: jax.Array
    aff_key: jax.Array       # (AFFINITY_SLOTS,) i32
    aff_ep: jax.Array        # (AFFINITY_SLOTS,) i32
    pool_req_id: jax.Array   # (I, C) i32
    pool_endpoint: jax.Array
    pool_svc: jax.Array
    pool_length: jax.Array
    pool_token: jax.Array
    pool_active: jax.Array   # (I, C) i32 (0/1)


#: The largest admission tile a compiled kernel fits in a v5e's default
#: scoped VMEM: an AOT compile of 1024 rows runs out, 512 fits.
MAX_TILE_ROWS = 512


def admit_tile_ok(block_r: int, R: int, *, interpret: bool) -> bool:
    """Whether ``block_r`` rows tile an ``R``-row admission batch: compiled
    tiles are whole 128-lane multiples (or the whole batch) of at most
    ``MAX_TILE_ROWS`` rows; the interpreter takes any tile."""
    return interpret or (min(block_r, R) <= MAX_TILE_ROWS
                         and tile_ok(block_r, R, 128, interpret=False))


def _pad_rows(block_r: int, req_id, svc, features, msg_bytes, rnd, gumbel,
              token=None):
    """Pad ragged batches with req_id=-1 rows (inert in-kernel: no counter,
    metric or pool touches); callers slice per-request outputs back."""
    R0, F = features.shape
    R = -(-R0 // block_r) * block_r
    if R != R0:
        pad = R - R0
        req_id = jnp.concatenate([req_id, jnp.full((pad,), -1, jnp.int32)])
        svc = jnp.concatenate([svc, jnp.zeros((pad,), svc.dtype)])
        features = jnp.concatenate(
            [features, jnp.zeros((pad, F), features.dtype)])
        msg_bytes = jnp.concatenate(
            [msg_bytes, jnp.zeros((pad,), msg_bytes.dtype)])
        rnd = jnp.concatenate([rnd, jnp.zeros((pad,), rnd.dtype)])
        gumbel = jnp.concatenate(
            [gumbel, jnp.zeros((pad, gumbel.shape[1]), gumbel.dtype)])
        if token is not None:
            token = jnp.concatenate([token, jnp.zeros((pad,), token.dtype)])
    return R, req_id, svc, features, msg_bytes, rnd, gumbel, token


def _launch_admit(req_id, svc, features, msg_bytes, state, free_i32, rnd,
                  gumbel, token, pool, *, block_r: int, fold: str,
                  interpret: bool | None):
    """Shared pallas_call plumbing for ``admit`` (pool=None) and
    ``admit_commit`` (pool = 5 incoming (I, C) i32 arrays)."""
    commit = pool is not None
    R0, F = features.shape
    interpret = resolve_interpret(interpret)
    if not admit_tile_ok(block_r, R0, interpret=interpret):
        raise ValueError(f"block_r={block_r} does not tile a compiled "
                         f"kernel over {R0} rows: use a multiple of 128 "
                         f"(or the whole batch) of at most "
                         f"{MAX_TILE_ROWS} rows")
    R, req_id, svc, features, msg_bytes, rnd, gumbel, token = _pad_rows(
        block_r, req_id, svc, features, msg_bytes, rnd, gumbel, token)
    grid = (R // block_r,)
    # flow ids are derived OUTSIDE the kernel (plain jnp, padded rows
    # included) so the kernel, the staged chain, the oracle and the host
    # router all hash through the one policy_defs.flow_hash
    fkey = policy_defs.flow_hash(features).astype(jnp.int32)
    tables = [state.svc_rule_start, state.svc_rule_count, state.rule_field,
              state.rule_value, state.rule_cluster, state.cluster_ep_start,
              state.cluster_ep_count, state.cluster_policy,
              state.ep_instance, state.ep_weight, state.ep_drained,
              state.ep_load, state.rr_cursor, state.maglev_table,
              state.aff_key, state.aff_ep, free_i32]
    S = state.svc_rule_start.shape[0]
    CL = state.cluster_ep_count.shape[0]
    E = state.ep_load.shape[0]
    A = state.aff_key.shape[0]
    I, C = free_i32.shape
    req = _row_spec(block_r)
    row = lambda a: a.astype(jnp.int32)[None, :]
    in_arrays = [row(req_id), row(svc), features, row(msg_bytes), row(rnd),
                 gumbel.astype(jnp.float32), row(fkey)]
    in_specs = [req, req,
                pl.BlockSpec((block_r, F), lambda r: (r, 0)),
                req, req,
                pl.BlockSpec((block_r, MAX_EPS_PER_CLUSTER),
                             lambda r: (r, 0)), req]
    if commit:
        in_arrays.append(row(token))
        in_specs.append(req)
    in_arrays += tables
    in_specs += [_table_spec(t.shape) for t in tables]
    if commit:
        in_arrays += list(pool)
        in_specs += [_table_spec((I, C))] * 5
    out_specs = [req] * 5 + [_table_spec((E,)), _table_spec((CL,)),
                             _table_spec((S,)), _table_spec((S,)),
                             _table_spec((2,)),
                             _table_spec((A,)), _table_spec((A,))]
    out_shape = [jax.ShapeDtypeStruct((1, R), jnp.int32)] * 5 \
        + [jax.ShapeDtypeStruct((E,), jnp.int32),
           jax.ShapeDtypeStruct((CL,), jnp.int32),
           jax.ShapeDtypeStruct((S,), jnp.int32),
           jax.ShapeDtypeStruct((S,), jnp.int32),
           jax.ShapeDtypeStruct((2,), jnp.int32),
           jax.ShapeDtypeStruct((A,), jnp.int32),
           jax.ShapeDtypeStruct((A,), jnp.int32)]
    if commit:
        out_specs += [_table_spec((I, C))] * 6
        out_shape += [jax.ShapeDtypeStruct((I, C), jnp.int32)] * 6
    o = pl.pallas_call(
        functools.partial(_admit_kernel, block_r=block_r, commit=commit,
                          fold=fold),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((E,), jnp.int32),
                        pltpu.VMEM((E,), jnp.int32),
                        pltpu.VMEM((CL,), jnp.int32),
                        pltpu.VMEM((I,), jnp.int32),
                        pltpu.VMEM((S,), jnp.int32),
                        pltpu.VMEM((S,), jnp.int32),
                        pltpu.VMEM((2,), jnp.int32),
                        pltpu.VMEM((A,), jnp.int32),
                        pltpu.VMEM((A,), jnp.int32)],
        interpret=interpret,
        name="xlb_admit_commit" if commit else "xlb_admit",
    )(*in_arrays)
    head = (o[0][0, :R0], o[1][0, :R0], o[2][0, :R0], o[3][0, :R0],
            o[4][0, :R0],
            o[5], o[6], o[7], o[8], o[9][0], o[9][1])
    return head + tuple(o[10:])


def admit(req_id, svc, features, msg_bytes, state, free_mask, rnd, gumbel, *,
          block_r: int = 256, fold: str | None = None,
          interpret: bool | None = None) -> AdmitResult:
    """Fused admission datapath over a request batch.

    req_id/svc/msg_bytes/rnd: (R,) i32 (req_id < 0 = padding; rnd = host
    PRNG draws for the random policy); features: (R, F) i32;
    gumbel: (R, MAX_EPS_PER_CLUSTER) f32 noise for the weighted policy;
    state: RoutingState; free_mask: (I, C) — nonzero/True = free slot.

    Sequential semantics (cross-checked bit-exactly against
    ``kernels.ref.admit_ref``): requests are processed in arrival order;
    every routable request advances its cluster's rr cursor and increments
    its endpoint's load counter immediately; requests that find no free pool
    slot are *held* and release their counter at the end of the batch.
    """
    R0, F = features.shape
    if R0 == 0:                         # empty batch: nothing to admit
        z = jnp.zeros((0,), jnp.int32)
        zs = jnp.zeros_like(state.svc_rule_start)
        return AdmitResult(
            z, z, z, z, z, state.ep_load,
            state.rr_cursor % jnp.maximum(state.cluster_ep_count, 1),
            zs, zs, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            state.aff_key, state.aff_ep)
    block_r = min(block_r, R0)
    # booleanize: the kernel cumsums the mask as per-slot counts, so an
    # integer mask cell > 1 would double-count free slots
    o = _launch_admit(req_id, svc, features, msg_bytes, state,
                      (free_mask != 0).astype(jnp.int32), rnd, gumbel,
                      None, None, block_r=block_r, fold=resolve_fold(fold),
                      interpret=interpret)
    return AdmitResult(*o)


def admit_commit(req_id, svc, features, msg_bytes, token, state,
                 pool_req_id, pool_endpoint, pool_svc, pool_length,
                 pool_token, pool_active, rnd, gumbel, *,
                 block_r: int = 256, fold: str | None = None,
                 interpret: bool | None = None) -> AdmitCommitResult:
    """``admit`` + in-kernel pool commit (the paper's full connect path).

    Same contract as ``admit`` with the free-slot mask derived from
    ``pool_active`` (~active = free); admitted requests additionally write
    req_id/endpoint/svc/length=0/token/active=1 at their (instance, slot)
    inside the kernel — no ``scatter_to_pool`` post-pass.  Bit-exact against
    ``kernels.ref.admit_commit_ref``.
    """
    R0, F = features.shape
    active_i32 = (pool_active != 0).astype(jnp.int32)   # booleanized 0/1
    pool = (pool_req_id.astype(jnp.int32), pool_endpoint.astype(jnp.int32),
            pool_svc.astype(jnp.int32), pool_length.astype(jnp.int32),
            pool_token.astype(jnp.int32))
    if R0 == 0:                         # empty batch: pool passes through
        z = jnp.zeros((0,), jnp.int32)
        zs = jnp.zeros_like(state.svc_rule_start)
        return AdmitCommitResult(
            z, z, z, z, z, state.ep_load,
            state.rr_cursor % jnp.maximum(state.cluster_ep_count, 1),
            zs, zs, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            state.aff_key, state.aff_ep, *pool, active_i32)
    block_r = min(block_r, R0)
    o = _launch_admit(req_id, svc, features, msg_bytes, state,
                      1 - active_i32, rnd, gumbel, token, pool,
                      block_r=block_r, fold=resolve_fold(fold),
                      interpret=interpret)
    return AdmitCommitResult(*o)
