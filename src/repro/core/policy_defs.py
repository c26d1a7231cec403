"""The policy-dispatch seam: every LB policy defined ONCE (DESIGN.md §9).

The four datapaths that select endpoints — the fused Pallas kernel
(``kernels/route_match.py``, both folds), the sequential numpy oracle
(``kernels/ref.py``), the staged jnp chain (``core/policies.py``) and the
host-side sidecar router (``core/sidecar.py``) — historically re-implemented
each policy four times with hand-kept agreement.  This module is the single
registry they all derive from: one :class:`PolicyDef` per policy carries

  * the enum value and CLI name (``serve.py --policy``),
  * state descriptors — which ``RoutingState`` fields the policy reads and
    writes in the datapath,
  * the per-policy shard **merge rule** consumed by the mesh-sharded
    admission (``kernels/shard_admit.py``): ``"cursor"`` (rr/random advance a
    per-cluster arrival counter → count-offset carry-in), ``"waterfill"``
    (least-request needs the closed-form load carry-in), ``"none"`` (hash /
    affinity selection is independent of carried load+cursor state — the
    embarrassingly shard-parallel case),
  * four lowering hooks: ``kernel_offset`` (one body serving BOTH the
    segment and onehot folds of the Pallas kernel), ``oracle_pick`` (the
    sequential per-request numpy reference), ``staged_offset`` (batched
    jnp) and ``host_pick`` (per-request numpy in the sidecar baselines).

Adding a policy is one ``PolicyDef`` in ``REGISTRY`` — every datapath,
including the sharded reconciliation, picks it up from here.

The hook contracts hand each hook a small namespace ("ctx") built by the
calling datapath; the fields are documented on each hook builder below.
This module deliberately imports nothing from ``repro.kernels`` (the kernels
import *it*), and not ``routing_table`` either (which re-exports the enum
from here) — it is the leaf of the dependency graph.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import numpy as np

# --------------------------------------------------------------------------- #
# The policy enum — THE single source of truth.  routing_table re-exports
# these; kernels/route_match.py, kernels/ref.py and core/policies.py import
# them from there (one definition site, asserted below).
# --------------------------------------------------------------------------- #
POLICY_RR = 0             # round-robin over eligible endpoints
POLICY_RANDOM = 1         # host-PRNG uniform over eligible endpoints
POLICY_LEAST_REQUEST = 2  # sequentially-consistent least outstanding
POLICY_WEIGHTED = 3       # Gumbel-max over log weights
POLICY_MAGLEV = 4         # Maglev consistent hash over the flow id
POLICY_AFFINITY = 5       # session stickiness: flow → endpoint cache,
#                           Maglev fallback on miss

#: CLI name → enum (``launch/serve.py --policy`` and benchmark knobs)
POLICY_NAMES = {
    "rr": POLICY_RR,
    "random": POLICY_RANDOM,
    "least_request": POLICY_LEAST_REQUEST,
    "weighted": POLICY_WEIGHTED,
    "maglev": POLICY_MAGLEV,
    "affinity": POLICY_AFFINITY,
}

#: Maglev permutation-table width per cluster.  Prime (every skip is
#: coprime → each endpoint's probe sequence is a full permutation) and
#: ~8× MAX_EPS_PER_CLUSTER so per-endpoint shares stay within ~±1 slot.
MAGLEV_TABLE_SIZE = 521

#: Direct-mapped session-affinity cache slots (flow_hash % slots).
AFFINITY_SLOTS = 512

#: Sentinel load for ineligible lanes — a python literal so Pallas kernels
#: can close over it (a jnp scalar would be verifier-rejected).
BIG = 2**30


# --------------------------------------------------------------------------- #
# Flow identity — one hash, every datapath.
# --------------------------------------------------------------------------- #


def flow_hash(features):
    """31-bit FNV-style flow id over the request's feature columns.

    Works on numpy AND jnp arrays (``(..., F)`` int32 → ``(...,)`` int32,
    always ≥ 0): integer math in uint32 wraps identically in both, so the
    kernel wrapper, the staged chain, the oracle and the host router all
    derive the same key from the same features.
    """
    if isinstance(features, np.ndarray):
        f = features.astype(np.uint32)
        h = np.full(f.shape[:-1], 0x811C9DC5, np.uint32)
        with np.errstate(over="ignore"):     # uint32 wraparound is the hash
            for j in range(f.shape[-1]):
                h = (h ^ f[..., j]) * np.uint32(0x01000193)
        return (h & np.uint32(0x7FFFFFFF)).astype(np.int32)
    import jax.numpy as jnp
    f = features.astype(jnp.uint32)
    h = jnp.full(f.shape[:-1], 0x811C9DC5, jnp.uint32)
    for j in range(f.shape[-1]):
        h = (h ^ f[..., j]) * jnp.uint32(0x01000193)
    return (h & jnp.uint32(0x7FFFFFFF)).astype(jnp.int32)


# --------------------------------------------------------------------------- #
# Maglev table construction (host side, numpy — the control plane's job).
# --------------------------------------------------------------------------- #


def _mix(x: int, salt: int) -> int:
    """Deterministic 32-bit scramble of an endpoint identity."""
    h = (int(x) ^ salt) & 0xFFFFFFFF
    h = (h * 0x01000193 + 0x811C9DC5) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0x5BD1E995) & 0xFFFFFFFF
    h ^= h >> 15
    return h


def _maglev_row(offsets: list[int], ids: list[int], T: int) -> np.ndarray:
    """One cluster's Maglev lookup row (T,) of WINDOW OFFSETS, -1 = empty.

    Canonical Maglev: endpoint k probes slots ``(offset_k + j·skip_k) % T``
    and claims the next untaken one, round-robin across endpoints, until the
    table is full — each endpoint owns ~T/E slots (max−min ≤ ~1).  Probe
    sequences are keyed on the endpoint's *identity hash* (``ids``, the
    instance-lane id), NOT its window position, so membership changes —
    swap-with-last compaction, window relocation, drain of a neighbour —
    leave surviving endpoints' claims nearly untouched (~1/E of slots remap
    per add/drain, the consistent-hash property the tests pin).
    """
    row = np.full((T,), -1, np.int32)
    if not offsets:
        return row
    E = len(offsets)
    offset = [_mix(i, 0x9E3779B9) % T for i in ids]
    skip = [_mix(i, 0x85EBCA6B) % (T - 1) + 1 for i in ids]
    ptr = [0] * E
    filled = 0
    while filled < T:
        for k in range(E):
            while True:
                c = (offset[k] + ptr[k] * skip[k]) % T
                ptr[k] += 1
                if row[c] < 0:
                    row[c] = offsets[k]
                    filled += 1
                    break
            if filled == T:
                break
    return row


def build_maglev_table(ep_start, ep_count, ep_instance, ep_drained,
                       table_size: int = MAGLEV_TABLE_SIZE) -> np.ndarray:
    """(CL, T) i32 Maglev table over every cluster's ELIGIBLE (non-drained)
    endpoints; rows of empty / fully-drained clusters stay -1 (the kernel
    then reports NO_ROUTE via the eligibility count, never a stale entry)."""
    cs = np.asarray(ep_start, np.int64)
    cc = np.asarray(ep_count, np.int64)
    inst = np.asarray(ep_instance, np.int64)
    dr = np.asarray(ep_drained, np.int64)
    CL = cs.shape[0]
    tab = np.full((CL, table_size), -1, np.int32)
    for c in range(CL):
        n = int(cc[c])
        if n <= 0:
            continue
        s = int(cs[c])
        offs = [j for j in range(n) if dr[s + j] == 0]
        ids = [int(inst[s + j]) for j in offs]
        tab[c] = _maglev_row(offs, ids, table_size)
    return tab


def maglev_row_inputs(cfg: dict, c: int) -> tuple:
    """The exact inputs one cluster's table row depends on — the control
    plane diffs this across a transaction to rebuild only dirty rows."""
    s = int(cfg["cluster_ep_start"][c])
    n = int(cfg["cluster_ep_count"][c])
    return (n, tuple(np.asarray(cfg["ep_instance"][s:s + n]).tolist()),
            tuple(np.asarray(cfg["ep_drained"][s:s + n]).tolist()))


# --------------------------------------------------------------------------- #
# Lowering hooks.  Each hook receives a ctx namespace built by its datapath:
#
# kernel ctx (route_match._admit_kernel, BOTH folds; (BR,) unless noted):
#   fold, block_r      static fold name / tile rows
#   policy, cl         per-request policy enum / clamped cluster id
#   routable, rank_c   eligibility mask / in-tile arrival rank within cluster
#   estart, count      cluster window start / raw window count
#   cnt1, cnt2         eligible-endpoint count (≥1 clamped / raw)
#   eidx, eok          (BR, WE) window endpoint indices / eligibility mask
#   rnd, fkey          host PRNG draw / flow id
#   gum                (BR, WE) Gumbel noise
#   loads, ew, ed      (E,) live loads / weights / drain mask
#   cs_vec, cc_vec     (CL,) cluster windows (for per-cluster fold tables)
#   cur_cl             per-request live rr cursor (cur_s[cl])
#   mg_tab             (CL, T) Maglev table
#   aff_key, aff_ep    (A,) affinity cache (tile-start snapshot)
#   kth(k)             window offset of the k-th eligible endpoint
#   cyc(k)             kth(k) with the segment fold's no-drain shortcut
#   seg_rank(ids, mask, n)  the fold-seam rank helper
#   window(t)          (BR, WE) window t[eidx] of an (E,) table
#   take(t, ids)       t[ids] of a 1-D table at in-range (BR,) ids
#   take2(t, r, c)     t[r, c] of a 2-D table at in-range (BR,) ids
#   first_true(m)      argmax of a (BR, W) bool mask along lanes
#   prefix_count(m)    inclusive running count of a (BR, W) mask along
#                      lanes
# Kernel hooks read tables only through these fold helpers: the onehot
# fold's forms are the ones Mosaic compiles (kernels/route_match.py).
#
# oracle ctx (ref.admit_ref; numpy, mutated in place by the loop):
#   loads, cur         (E,)/(CL,) live counters
#   cs, cc, E          cluster windows / endpoint capacity
#   drained            (E,) drain mask
#   rnd, fkey, wt_off  per-request draws / flow ids / precomputed
#                      weighted offsets
#   mg, T              (CL, T) Maglev table / its width
#   affk, affe, A      affinity cache arrays (hooks may write) / slots
#
# staged ctx (policies.select; jnp, batched):
#   state              RoutingState
#   cl, start, count   clamped cluster / window start / raw count
#   cnt1, ok, idx      eligible count (≥1) / (B, WE) masks / indices
#   rank               arrival rank within cluster
#   rnd, fkey, gum     PRNG draws / flow ids / Gumbel noise
#   kth(k)             k-th eligible offset
#
# host ctx (sidecar.HostRouter; one request at a time, numpy):
#   t                  the router's mutable numpy RoutingState copy
#   rng                the router's PRNG
#   E                  endpoint capacity
# Hooks return WINDOW OFFSETS (kernel/staged) or ABSOLUTE endpoint indices
# (oracle/host).
# --------------------------------------------------------------------------- #

import types  # noqa: E402

import jax.numpy as jnp  # noqa: E402  (hooks below are jnp-lowered)
import jax  # noqa: E402


class KernelCtx(types.SimpleNamespace):
    """The kernel-hook ctx (field contract in the comment above) — a plain
    namespace the Pallas kernel fills with traced arrays + fold helpers."""


class StagedCtx(types.SimpleNamespace):
    """The staged-hook ctx (``core/policies.py`` fills it per batch)."""


class OracleCtx(types.SimpleNamespace):
    """The oracle-hook ctx (``kernels/ref.py`` fills it with live numpy
    arrays; affinity hooks mutate ``affk``/``affe`` in place)."""


# ---- round robin ---------------------------------------------------------- #

def _rr_kernel(ctx):
    return ctx.cyc((ctx.cur_cl + ctx.rank_c) % ctx.cnt1)


def _rr_oracle(o, r, c, elig):
    return elig[o.cur[c] % len(elig)]


def _rr_staged(s):
    return s.kth((s.state.rr_cursor[s.cl] + s.rank) % s.cnt1)


def _rr_host(h, c, elig, feats):
    ep = elig[h.t.rr_cursor[c] % len(elig)]
    h.t.rr_cursor[c] += 1
    return ep


# ---- random --------------------------------------------------------------- #

def _random_kernel(ctx):
    return ctx.cyc(ctx.rnd % ctx.cnt1)


def _random_oracle(o, r, c, elig):
    return elig[o.rnd[r] % len(elig)]


def _random_staged(s):
    return s.kth(s.rnd % s.cnt1)


def _random_host(h, c, elig, feats):
    return elig[h.rng.randint(0, len(elig))]


# ---- least request -------------------------------------------------------- #

def _lr_kernel(ctx):
    """Sequential least-request without a per-request scan: the request with
    in-tile cluster rank ρ owns the ρ-th smallest ticket of the multiset
    {load_j + t : t ≥ 0} ordered by (value, j) — water-filling closed form
    of "argmin then increment".  The segment fold reads the level from
    per-cluster sorted-prefix tables (one (CL, WE) sort per tile); the
    onehot fold finds it by a static-depth binary search (Mosaic-friendly,
    no sort)."""
    eok, eidx, rank_c = ctx.eok, ctx.eidx, ctx.rank_c
    load = jnp.where(eok, ctx.window(ctx.loads), BIG)      # (BR, WE)

    def pick(v, n_prev):
        m = rank_c - n_prev                # rank among value-v ties
        elig = load <= v[:, None]
        ec = ctx.prefix_count(elig)
        return ctx.first_true(elig & (ec == (m + 1)[:, None]))

    if ctx.fold == "segment":
        # per-CLUSTER water-fill tables: every request of a cluster shares
        # the same tile-start load multiset, so the ticket geometry —
        # sorted eligible loads ``cls_``, inclusive prefix ``cpin``,
        # segment starts ``cS`` (tickets below level ls[k]) — is computed
        # once per cluster on (CL, WE) arrays (tiny) and each request only
        # gathers scalars from it: k* engaged endpoints where
        # cS[k*] ≤ ρ < cS[k*+1], then v = ⌈(ρ+1+Σ_{i<k*} l_i)/k*⌉ − 1.
        # BIG lanes clamp to lo+BR so they never engage (and the prefix
        # sums stay far from int32 range for sane load counters ≥ 0).
        CL = ctx.cs_vec.shape[0]
        WE = eidx.shape[1]
        E = ctx.loads.shape[0]
        cwin = jax.lax.broadcasted_iota(jnp.int32, (CL, WE), 1)
        ceidx = jnp.clip(ctx.cs_vec[:, None] + cwin, 0, E - 1)
        ceok = (cwin < ctx.cc_vec[:, None]) & (ctx.ed[ceidx] == 0)
        cload = jnp.where(ceok, ctx.loads[ceidx], BIG)
        clo = jnp.min(cload, axis=1)
        cls_ = jnp.sort(jnp.minimum(cload, clo[:, None] + ctx.block_r),
                        axis=1)
        cpin = jnp.cumsum(cls_, axis=1)                # inclusive prefix
        cS = (cwin + 1) * cls_ - cpin                  # nondecreasing
        kstar = jnp.sum((cS[ctx.cl] <= rank_c[:, None]).astype(jnp.int32),
                        axis=1)                        # ≥ 1 (cS[0] == 0)
        pk = cpin.reshape(-1)[ctx.cl * WE + kstar - 1]  # Σ engaged loads
        v = (rank_c + pk + kstar) // kstar - 1
        return pick(v, kstar * v - pk)
    # onehot: static-depth binary search for the ticket level
    lo = jnp.min(load, axis=1)
    hi = lo + rank_c
    tgt = rank_c + 1
    for _ in range(max(ctx.block_r, 2).bit_length()):
        mid = (lo + hi) // 2
        n_mid = jnp.sum(jnp.maximum(mid[:, None] - load + 1, 0), axis=1)
        ge = n_mid >= tgt
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    v = lo
    return pick(v, jnp.sum(jnp.maximum(v[:, None] - load, 0), axis=1))


def _lr_oracle(o, r, c, elig):
    return elig[int(np.argmin([o.loads[e] for e in elig]))]


def _lr_staged(s):
    # vectorised batch semantics: the r-th request (arrival order) of a
    # cluster takes the r-th LEAST-loaded endpoint, emulating sequential
    # per-request counters; ineligible endpoints sort behind INT_MAX
    load = jnp.where(s.ok, s.state.ep_load[s.idx],
                     jnp.iinfo(jnp.int32).max)
    by_load = jnp.argsort(load, axis=1).astype(jnp.int32)
    return jnp.take_along_axis(by_load, (s.rank % s.cnt1)[:, None], 1)[:, 0]


def _lr_host(h, c, elig, feats):
    return elig[int(np.argmin(h.t.ep_load[elig]))]


# ---- weighted ------------------------------------------------------------- #

def _wt_kernel(ctx):
    w = jnp.where(ctx.eok, ctx.window(ctx.ew), 0.0)
    return jnp.argmax(jnp.where(ctx.eok, jnp.log(w + 1e-9) + ctx.gum,
                                -jnp.inf), axis=1).astype(jnp.int32)


def _wt_oracle(o, r, c, elig):
    # precomputed via jnp so f32 rounding / argmax tie-breaks match the
    # kernel bit-exactly (see ref.admit_ref)
    return min(max(o.cs[c] + o.wt_off[r], 0), o.E - 1)


def _wt_staged(s):
    w = jnp.where(s.ok, s.state.ep_weight[s.idx], 0.0)
    return jnp.argmax(jnp.where(s.ok, jnp.log(w + 1e-9) + s.gum, -jnp.inf),
                      axis=1).astype(jnp.int32)


def _wt_host(h, c, elig, feats):
    w = np.maximum(h.t.ep_weight[elig], 0.0)
    tot = float(w.sum())
    if tot <= 0.0:
        return elig[h.rng.randint(0, len(elig))]
    return elig[h.rng.choice(len(elig), p=w / tot)]


# ---- maglev consistent hash ----------------------------------------------- #
# Selection rule (identical in all four datapaths): look the flow id up in
# the cluster's permutation row → a window offset.  The entry is trusted
# only if it is inside the window AND its endpoint is not drained (the
# drain mask gates BEFORE the table result is used — a mid-serve drain the
# table has not been rebuilt for can never route onto a drained endpoint);
# otherwise fall back to hash-cycling over the k-th eligible endpoint.
# A cluster with zero eligible endpoints is unroutable upstream (cnt2 == 0
# → NO_ROUTE), exactly like the other policies.

def _maglev_kernel(ctx):
    T = ctx.mg_tab.shape[1]
    t = ctx.take2(ctx.mg_tab, ctx.cl, ctx.fkey % T)        # window offsets
    te = jnp.clip(ctx.estart + t, 0, ctx.ed.shape[0] - 1)
    t_ok = (t >= 0) & (t < ctx.count) & (ctx.take(ctx.ed, te) == 0)
    return jnp.where(t_ok, t, ctx.cyc(ctx.fkey % ctx.cnt1)
                     ).astype(jnp.int32)


def _maglev_oracle(o, r, c, elig):
    key = int(o.fkey[r])
    t = int(o.mg[c, key % o.T])
    if 0 <= t < o.cc[c]:
        e = min(max(o.cs[c] + t, 0), o.E - 1)
        if o.drained[e] == 0:
            return e
    return elig[key % len(elig)]


def _maglev_staged(s):
    T = s.state.maglev_table.shape[1]
    t = s.state.maglev_table[s.cl, s.fkey % T]
    te = jnp.clip(s.start + t, 0, s.state.ep_drained.shape[0] - 1)
    t_ok = (t >= 0) & (t < s.count) & (s.state.ep_drained[te] == 0)
    return jnp.where(t_ok, t, s.kth(s.fkey % s.cnt1)).astype(jnp.int32)


# ---- session affinity ----------------------------------------------------- #
# Snapshot-pure semantics (the property that makes tile-carried, batched
# and sharded evaluation bit-identical to the sequential oracle): a HIT
# requires stored_key == flow id AND the cached endpoint inside the
# request's cluster window AND not drained; a MISS falls back to the pure
# stateless Maglev pick (a function of the flow id and static tables only);
# the cache is written only when the slot is empty or already owns this
# key — never evicting another flow.  Because the fallback is pure, a
# request that reads a stale snapshot routes identically to one that saw
# the write, and at most one distinct value is ever written per slot per
# batch (first writer in arrival order wins).

def _aff_hit(ctx):
    A = ctx.aff_key.shape[0]
    s = ctx.fkey % A
    ak = ctx.take(ctx.aff_key, s)
    ae = ctx.take(ctx.aff_ep, s)
    aec = jnp.clip(ae, 0, ctx.ed.shape[0] - 1)
    hit = ((ak == ctx.fkey) & (ae >= ctx.estart)
           & (ae < ctx.estart + ctx.count) & (ctx.take(ctx.ed, aec) == 0))
    return s, ak, ae, hit


def _affinity_kernel(ctx):
    _, _, ae, hit = _aff_hit(ctx)
    return jnp.where(hit, ae - ctx.estart,
                     _maglev_kernel(ctx)).astype(jnp.int32)


def affinity_kernel_update(ctx, ep):
    """Fold this tile's affinity writes into the carried cache (both folds).

    ``ep`` is the post-selection absolute endpoint per request.  First
    writer per slot (in-tile arrival order) wins — `.at[].set` gives no
    ordering guarantee under duplicate indices, so winners are picked by
    the fold-seam rank first.  Returns (new_aff_key, new_aff_ep)."""
    A = ctx.aff_key.shape[0]
    s, ak, _, hit = _aff_hit(ctx)
    want = (ctx.routable & (ctx.policy == POLICY_AFFINITY) & ~hit
            & ((ak == -1) | (ak == ctx.fkey)))
    rank_w, _ = ctx.seg_rank(jnp.where(want, s, A), want, A)
    win = want & (rank_w == 0)
    if ctx.fold == "segment":
        tgt = jnp.where(win, s, A)
        nk = ctx.aff_key.at[tgt].set(ctx.fkey, mode="drop")
        ne = ctx.aff_ep.at[tgt].set(ep, mode="drop")
        return nk, ne
    oh = (jnp.where(win, s, A)[:, None] == jax.lax.broadcasted_iota(
        jnp.int32, (win.shape[0], A), 1)).astype(jnp.int32)
    wrote = jnp.sum(oh, axis=0) > 0
    nk = jnp.where(wrote, jnp.sum(oh * ctx.fkey[:, None], axis=0),
                   ctx.aff_key)
    ne = jnp.where(wrote, jnp.sum(oh * ep[:, None], axis=0), ctx.aff_ep)
    return nk, ne


def _affinity_oracle(o, r, c, elig):
    key = int(o.fkey[r])
    s = key % o.A
    ae = int(o.affe[s])
    if (int(o.affk[s]) == key and o.cs[c] <= ae < o.cs[c] + o.cc[c]
            and o.drained[ae] == 0):
        return ae
    ep = _maglev_oracle(o, r, c, elig)
    if o.affk[s] == -1 or o.affk[s] == key:     # first admit writes through
        o.affk[s] = key
        o.affe[s] = ep
    return ep


def _affinity_staged(s):
    A = s.state.aff_key.shape[0]
    sl = s.fkey % A
    ak = s.state.aff_key[sl]
    ae = s.state.aff_ep[sl]
    aec = jnp.clip(ae, 0, s.state.ep_drained.shape[0] - 1)
    hit = ((ak == s.fkey) & (ae >= s.start) & (ae < s.start + s.count)
           & (s.state.ep_drained[aec] == 0))
    return jnp.where(hit, ae - s.start, _maglev_staged(s)).astype(jnp.int32)


def affinity_staged_update(s, ep, routable, policy):
    """Batch-snapshot cache update for the staged chain (bit-identical to
    the sequential write rule — see the purity argument above).  Returns
    (new_aff_key, new_aff_ep)."""
    from repro.core import relay
    A = s.state.aff_key.shape[0]
    sl = s.fkey % A
    ak = s.state.aff_key[sl]
    ae = s.state.aff_ep[sl]
    aec = jnp.clip(ae, 0, s.state.ep_drained.shape[0] - 1)
    hit = ((ak == s.fkey) & (ae >= s.start) & (ae < s.start + s.count)
           & (s.state.ep_drained[aec] == 0))
    want = (routable & (policy == POLICY_AFFINITY) & ~hit
            & ((ak == -1) | (ak == s.fkey)))
    rank_w, _ = relay.positions_sort(jnp.where(want, sl, A), A + 1)
    win = want & (rank_w == 0)
    tgt = jnp.where(win, sl, A)
    nk = s.state.aff_key.at[tgt].set(s.fkey, mode="drop")
    ne = s.state.aff_ep.at[tgt].set(ep, mode="drop")
    return nk, ne


class _HostOracleView:
    """Adapt a HostRouter + one request to the oracle-ctx field contract,
    so maglev/affinity are literally the oracle hooks run per request (the
    sidecar is sequential by construction — exact sharing, zero drift)."""

    def __init__(self, h):
        t = h.t
        self.loads = t.ep_load
        self.cur = t.rr_cursor
        self.cs = t.cluster_ep_start
        self.cc = t.cluster_ep_count
        self.E = t.ep_instance.shape[0]
        self.drained = t.ep_drained
        self.mg = t.maglev_table
        self.T = t.maglev_table.shape[1]
        self.affk = t.aff_key
        self.affe = t.aff_ep
        self.A = t.aff_key.shape[0]
        self.fkey = [0]              # filled per request by the host hook


def _host_view(h, feats):
    o = _HostOracleView(h)
    o.fkey = np.array([flow_hash(np.asarray(feats, np.int32))])
    return o


def _maglev_host(h, c, elig, feats):
    return _maglev_oracle(_host_view(h, feats), 0, c, elig)


def _affinity_host(h, c, elig, feats):
    return _affinity_oracle(_host_view(h, feats), 0, c, elig)


# --------------------------------------------------------------------------- #
# The registry.
# --------------------------------------------------------------------------- #


@dataclasses.dataclass(frozen=True)
class PolicyDef:
    """One LB policy, defined once for every datapath."""

    name: str
    enum: int
    state_reads: tuple[str, ...]         # RoutingState fields consulted
    state_writes: tuple[str, ...]        # RoutingState fields mutated
    shard_merge: str                     # 'cursor' | 'waterfill' | 'none'
    kernel_offset: Callable[[Any], Any]  # Pallas, both folds → window offs
    oracle_pick: Callable                # sequential numpy → absolute ep
    staged_offset: Callable[[Any], Any]  # batched jnp → window offs
    host_pick: Callable                  # sidecar numpy → absolute ep
    gate: bool = True                    # segment fold: lax.cond-skip when
    #                                      no cluster uses this policy


REGISTRY: tuple[PolicyDef, ...] = (
    PolicyDef("rr", POLICY_RR,
              ("rr_cursor",), ("rr_cursor", "ep_load"), "cursor",
              _rr_kernel, _rr_oracle, _rr_staged, _rr_host, gate=False),
    PolicyDef("random", POLICY_RANDOM,
              (), ("ep_load",), "cursor",
              _random_kernel, _random_oracle, _random_staged, _random_host),
    PolicyDef("least_request", POLICY_LEAST_REQUEST,
              ("ep_load",), ("ep_load",), "waterfill",
              _lr_kernel, _lr_oracle, _lr_staged, _lr_host),
    PolicyDef("weighted", POLICY_WEIGHTED,
              ("ep_weight",), ("ep_load",), "none",
              _wt_kernel, _wt_oracle, _wt_staged, _wt_host),
    PolicyDef("maglev", POLICY_MAGLEV,
              ("maglev_table", "ep_drained"), ("ep_load",), "none",
              _maglev_kernel, _maglev_oracle, _maglev_staged, _maglev_host),
    PolicyDef("affinity", POLICY_AFFINITY,
              ("aff_key", "aff_ep", "maglev_table", "ep_drained"),
              ("aff_key", "aff_ep", "ep_load"), "none",
              _affinity_kernel, _affinity_oracle, _affinity_staged,
              _affinity_host),
)

BY_ENUM: dict[int, PolicyDef] = {p.enum: p for p in REGISTRY}

#: enums whose shard merge rule needs the water-fill load carry-in
WATERFILL_ENUMS: tuple[int, ...] = tuple(
    p.enum for p in REGISTRY if p.shard_merge == "waterfill")

# import-time divergence guard: the registry is dense over 0..N-1, names
# are unique and agree with POLICY_NAMES — any drift between the enum
# constants above and the registry entries fails at import, not at runtime.
assert tuple(p.enum for p in REGISTRY) == tuple(range(len(REGISTRY))), \
    "policy registry enums must be dense and ordered"
assert {p.name: p.enum for p in REGISTRY} == POLICY_NAMES, \
    "POLICY_NAMES and REGISTRY disagree"
assert (POLICY_RR, POLICY_RANDOM, POLICY_LEAST_REQUEST, POLICY_WEIGHTED,
        POLICY_MAGLEV, POLICY_AFFINITY) == tuple(range(6)), \
    "policy enum constants drifted"
