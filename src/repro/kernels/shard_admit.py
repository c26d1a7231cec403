"""Mesh-sharded admission: many ingress hosts feed one fleet (ROADMAP
scale-out).

The paper's headline scenario is 50+ co-located instances fed from multiple
hosts; here the admission batch is split ``(R/M,)`` over a mesh axis and the
fused admit kernel (``route_match.admit``) runs per shard against replicated
routing tables, followed by ONE collective reconciliation pass.  The result
is **bit-exact** against single-shard ``admit_commit`` on the concatenated
batch — the deterministic merge rule is *shard-major order*: shard 0's rows
are "first", shard 1's follow, exactly as if one host had ingested the
concatenation (``kernels/ref.py::admit_sharded_ref`` pins this contract).

How sequential consistency survives the fan-out (DESIGN.md §7): the fused
kernel's carried VMEM counters make request ``i`` visible to request
``i+1`` *within* a shard; across shards the same effect comes from offsetting
each shard's kernel *inputs* by a closed form of the preceding shards'
per-cluster routable counts (one cheap match pass + ``all_gather``):

  * **rr cursors** carry raw counts (the PR-4 trick): shard ``s`` starts from
    ``rr_cursor + prev_counts`` and the final cursor is reconciled as
    ``(rr_cursor + Σ counts) mod window`` — shard-count independent.
  * **least-request loads** advance by a *water-fill*: admitting ``k``
    requests to a cluster produces a load multiset that depends only on
    ``k`` (request ``ρ`` takes the ``ρ``-th smallest ticket of
    ``{load_j + t}``, ties by window offset), so shard ``s`` water-fills
    ``prev_counts`` into the initial loads analytically and its local kernel
    continues bit-exactly where shard ``s-1`` "left off".
  * **random / weighted** consume per-request host PRNG draws — row-aligned
    with the batch split, order-free already.
  * **slot allocation** runs the local kernel against an all-free mask so
    its ``slot`` output *is* the local per-instance arrival rank; global
    ranks (prev-shard instance counts + local rank, one more ``all_gather``)
    are then matched against the true global free mask, which also decides
    held requests globally.

Everything the datapath owns reconciles in one collective pass:
``jax.lax.psum`` over per-shard ``ep_load`` deltas, held releases,
per-service metrics and the ``no_route``/``held`` counts; pool commits are
relayed to their owner shards (the pool is ``(I/M,)``-sharded) through the
``relay_dispatch`` counting-sort + ``all_to_all`` hop of ``core/relay.py`` —
the same collective schedule ``sharded_apply`` uses for the i-sock relay.

Each shard's kernel launch is gated by ``lax.cond`` on "any valid local
rows", so an all-padding shard (an idle ingress host) skips the kernel
entirely; the collectives always run on every shard (SPMD-uniform).
"""

from __future__ import annotations

from functools import lru_cache

import jax
import jax.numpy as jnp

from repro.core import policy_defs, relay, router
from repro.core.routing_table import MAX_EPS_PER_CLUSTER, RoutingState
from repro.kernels import completion as _cp
from repro.kernels import route_match as _rm
from repro.kernels.backend import resolve_fold, resolve_interpret
from repro.kernels.completion import CompleteResult
from repro.kernels.route_match import (BIG, AdmitCommitResult, AdmitResult)


def cluster_windows(state: RoutingState) -> tuple[jax.Array, jax.Array]:
    """Per-cluster endpoint window gathers: (ceidx, ceok) both (CL, WE).
    ``ceok`` marks lanes that are in-window AND not draining — the eligible
    set every selection path uses."""
    E = state.ep_load.shape[0]
    CL = state.cluster_ep_count.shape[0]
    WE = MAX_EPS_PER_CLUSTER
    cwin = jax.lax.broadcasted_iota(jnp.int32, (CL, WE), 1)
    ceidx = jnp.clip(state.cluster_ep_start[:, None] + cwin, 0, E - 1)
    ceok = (cwin < state.cluster_ep_count[:, None]) \
        & (state.ep_drained[ceidx] == 0)
    return ceidx, ceok


def waterfill_lr(state: RoutingState, k_cl: jax.Array) -> jax.Array:
    """``ep_load`` after sequentially admitting ``k_cl[c]`` requests into
    each LEAST_REQUEST cluster ``c`` — the closed form of "argmin then
    increment" repeated k times (ticket multiset ``{load_j + t}`` ordered by
    (value, window offset); the k taken tickets raise every engaged endpoint
    to the water level ``v`` and the first ``m`` at-level endpoints one
    higher).  Non-LR clusters pass through untouched: their loads are never
    read by selection, so only the LR multiset must match the sequential
    reference.  Bit-exact vs ``ref.admit_ref`` processing k requests."""
    E = state.ep_load.shape[0]
    ceidx, ceok = cluster_windows(state)
    load = jnp.where(ceok, state.ep_load[ceidx], BIG)   # (CL, WE)
    k = jnp.maximum(k_cl.astype(jnp.int32), 0)
    lo = jnp.min(load, axis=1)
    # lanes above lo+k never engage for k requests; clamping keeps the
    # ticket counts far from int32 range when ineligible lanes read BIG
    lcl = jnp.minimum(load, (lo + k)[:, None])
    hi = lo + k
    # smallest v with #tickets(value <= v) >= k  (static-depth search; the
    # k = 0 case degenerates to v = lo and an identity update)
    for _ in range(32):
        mid = lo + (hi - lo) // 2
        n_le = jnp.sum(jnp.maximum(mid[:, None] - lcl + 1, 0), axis=1)
        ge = n_le >= k
        hi = jnp.where(ge, mid, hi)
        lo = jnp.where(ge, lo, mid + 1)
    v = lo
    n_below = jnp.sum(jnp.maximum(v[:, None] - lcl, 0), axis=1)
    m_rem = k - n_below                    # value-v tickets taken
    engaged = ceok & (lcl <= v[:, None])   # v < min+k, so clamp never lies
    cum = jnp.cumsum(engaged.astype(jnp.int32), axis=1)
    extra = (engaged & (cum <= m_rem[:, None])).astype(jnp.int32)
    real = jnp.where(ceok, state.ep_load[ceidx], 0)
    newl = jnp.maximum(real, v[:, None]) + extra
    # registry merge rule: every policy whose shard_merge is "waterfill"
    # carries its load counters through this closed form (policy_defs)
    is_wf = jnp.zeros_like(state.cluster_policy, dtype=bool)
    for _e in policy_defs.WATERFILL_ENUMS:
        is_wf = is_wf | (state.cluster_policy == _e)
    apply = ceok & is_wf[:, None] & (k > 0)[:, None]
    # windows are disjoint, so every applied lane owns a unique slot
    tgt = jnp.where(apply, ceidx, E).reshape(-1)
    return state.ep_load.at[tgt].set(newl.reshape(-1), mode="drop")


def _bincount(ids, vals, length: int):
    """Masked scatter-add fold (ids >= length drop), (length,) i32."""
    return jnp.zeros((length,), jnp.int32).at[ids].add(
        vals.astype(jnp.int32), mode="drop")


def _prefix_before(gathered: jax.Array, m) -> jax.Array:
    """Sum of the per-shard rows strictly before shard ``m``: the exclusive
    scan giving each shard its carried-counter offset."""
    M = gathered.shape[0]
    mask = jnp.arange(M) < m
    return jnp.sum(jnp.where(mask[:, None], gathered, 0), axis=0)


def _shard_body(rid, sv, feats, mb, tok, rnd, gum, state: RoutingState,
                preq, pep, psvc, plen, ptok, pact, *, axis: str,
                block_r: int, fold: str, interpret: bool):
    """shard_map body: local fused admit + the collective reconciliation."""
    M = jax.lax.axis_size(axis)
    m = jax.lax.axis_index(axis)
    E = state.ep_load.shape[0]
    CL = state.cluster_ep_count.shape[0]
    S = state.svc_rule_start.shape[0]
    I_loc, C = preq.shape
    I = I_loc * M
    R_loc = rid.shape[0]

    # ---- phase 1: match + eligibility -> per-cluster routable counts ---- #
    valid = rid >= 0
    svc_c = jnp.clip(sv, 0, S - 1)
    cluster = jnp.where(valid, router.match_cluster(state, svc_c, feats), -1)
    _, ceok = cluster_windows(state)
    ecnt = jnp.sum(ceok.astype(jnp.int32), axis=1)          # (CL,)
    clm = jnp.maximum(cluster, 0)
    routable = valid & (cluster >= 0) & (ecnt[clm] > 0)
    cnt_cl = _bincount(jnp.where(routable, clm, CL), jnp.ones_like(clm), CL)
    all_cl = jax.lax.all_gather(cnt_cl, axis)               # (M, CL)
    prev_cl = _prefix_before(all_cl, m)
    total_cl = jnp.sum(all_cl, axis=0)

    # ---- phase 2: offset the carried-counter inputs --------------------- #
    adj_load = waterfill_lr(state, prev_cl)
    adj_cur = state.rr_cursor + prev_cl        # raw carry; modulo at emit
    st_local = state._replace(ep_load=adj_load, rr_cursor=adj_cur)

    # ---- phase 3: local fused admit kernel (all-free mask) -------------- #
    # n_free = R_loc >= any local instance count, so nothing is held inside
    # the kernel and its ``slot`` output IS the local per-instance arrival
    # rank; held/slots resolve globally in phase 4.  An all-padding shard
    # skips the kernel (the collectives below still run on every shard).
    free_all = jnp.ones((I, R_loc), jnp.int32)

    def run(_):
        return _rm.admit(rid, sv, feats, mb, st_local, free_all, rnd, gum,
                         block_r=block_r, fold=fold, interpret=interpret)

    def skip(_):
        neg = jnp.full((R_loc,), -1, jnp.int32)
        z = jnp.zeros((R_loc,), jnp.int32)
        zs = jnp.zeros((S,), jnp.int32)
        return AdmitResult(
            neg, neg, neg, neg, z, adj_load,
            adj_cur % jnp.maximum(state.cluster_ep_count, 1), zs, zs,
            jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            state.aff_key, state.aff_ep)

    res = jax.lax.cond(jnp.any(valid), run, skip, 0)

    # ---- phase 4: global slot allocation + psum reconciliation ---------- #
    rt = res.ok > 0                            # == routable (all-free mask)
    instc = jnp.clip(res.instance, 0, I - 1)
    local_rank = jnp.where(rt, res.slot, 0)
    cnt_i = _bincount(jnp.where(rt, instc, I), jnp.ones_like(instc), I)
    prev_i = _prefix_before(jax.lax.all_gather(cnt_i, axis), m)
    g_rank = prev_i[instc] + local_rank

    act_all = jax.lax.all_gather(pact, axis).reshape(I, C)
    free = (act_all == 0).astype(jnp.int32)
    fprefix = jnp.cumsum(free, axis=1)                      # (I, C)
    ok = rt & (g_rank < fprefix[:, C - 1][instc])
    hit = (free[instc] > 0) & (fprefix[instc] == (g_rank + 1)[:, None])
    slot = jnp.where(ok, jnp.argmax(hit, axis=1).astype(jnp.int32), -1)
    held = rt & ~ok

    epc = jnp.maximum(res.endpoint, 0)
    one = jnp.ones((R_loc,), jnp.int32)
    delta = res.ep_load - adj_load             # local increments, no release
    held_rel = _bincount(jnp.where(held, epc, E), one, E)
    ep_load = state.ep_load + jax.lax.psum(delta, axis) \
        - jax.lax.psum(held_rel, axis)

    # the kernel counted every routable request (nothing held locally);
    # subtract the globally-held ones before the metric psum
    held_svc = jnp.where(held & (sv < S), svc_c, S)
    sreq = jax.lax.psum(res.svc_requests - _bincount(held_svc, one, S), axis)
    stx = jax.lax.psum(res.svc_tx_bytes - _bincount(held_svc, mb, S), axis)
    no_route = jax.lax.psum(res.no_route, axis)
    held_n = jax.lax.psum(jnp.sum(held.astype(jnp.int32)), axis)
    rr_cursor = (state.rr_cursor + total_cl) \
        % jnp.maximum(state.cluster_ep_count, 1)

    # affinity-cache reconciliation: each shard's local kernel wrote its
    # cache against the same replicated snapshot, and the miss fallback is a
    # pure function of the flow key (policy_defs: snapshot-pure semantics),
    # so concurrent proposals for one slot agree on the value whenever the
    # sequential reference would have produced a hit.  Shard-major merge:
    # the lowest shard proposing a change to a slot wins — exactly the
    # first-writer rule of the concatenated sequential batch.
    gk = jax.lax.all_gather(res.aff_key, axis)              # (M, A)
    ge = jax.lax.all_gather(res.aff_ep, axis)
    prop = (gk != state.aff_key[None, :]) | (ge != state.aff_ep[None, :])
    has = jnp.any(prop, axis=0)
    m1 = jnp.argmax(prop, axis=0)              # first shard with a proposal
    aff_key = jnp.where(has,
                        jnp.take_along_axis(gk, m1[None, :], axis=0)[0],
                        state.aff_key)
    aff_ep = jnp.where(has,
                       jnp.take_along_axis(ge, m1[None, :], axis=0)[0],
                       state.aff_ep)

    # ---- phase 5: relay pool commits to their owner shards -------------- #
    # payload rows (req_id, endpoint, svc, token, slot, ok) counting-sorted
    # into per-instance pools, one all_to_all hop moves each pool to the
    # shard owning that instance slice (cf. relay.sharded_apply); admitted
    # global ranks are < C, so capacity C per source never drops a commit.
    x = jnp.stack([rid, res.endpoint, sv, tok, slot,
                   ok.astype(jnp.int32)], axis=1)           # (R_loc, 6)
    buf, _ = relay.relay_dispatch(x, jnp.where(ok, instc, I), I, C)
    recv = jax.lax.all_to_all(buf.reshape(M, I_loc, C, 6), axis,
                              split_axis=0, concat_axis=0, tiled=False)
    rows = recv.reshape(M * I_loc * C, 6)
    jj = jax.lax.broadcasted_iota(jnp.int32, (M, I_loc, C), 1).reshape(-1)
    rok = rows[:, 5] > 0
    jx = jnp.where(rok, jj, I_loc)                          # invalid -> drop
    sx = jnp.where(rok, rows[:, 4], 0)
    preq = preq.at[jx, sx].set(rows[:, 0], mode="drop")
    pep = pep.at[jx, sx].set(rows[:, 1], mode="drop")
    psvc = psvc.at[jx, sx].set(rows[:, 2], mode="drop")
    plen = plen.at[jx, sx].set(jnp.zeros_like(rows[:, 0]), mode="drop")
    ptok = ptok.at[jx, sx].set(rows[:, 3], mode="drop")
    pact = pact.at[jx, sx].set(jnp.ones_like(rows[:, 0]), mode="drop")

    return (cluster, res.endpoint, res.instance, slot, ok.astype(jnp.int32),
            ep_load, rr_cursor, sreq, stx, no_route, held_n,
            aff_key, aff_ep,
            preq, pep, psvc, plen, ptok, pact)


@lru_cache(maxsize=None)
def _build(mesh, axis: str, R_loc: int, block_r: int, fold: str,
           interpret: bool):
    """One compiled shard_map program per (mesh, axis, plan, local shape)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    body = partial(_shard_body, axis=axis, block_r=block_r, fold=fold,
                   interpret=interpret)
    sh = P(axis)
    rep = P()
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sh, sh, sh, sh, sh, sh, sh, rep) + (sh,) * 6,
        out_specs=(sh,) * 5 + (rep,) * 8 + (sh,) * 6,
        check_vma=False)
    return jax.jit(f)


def admit_commit_sharded(req_id, svc, features, msg_bytes, token,
                         state: RoutingState, pool_req_id, pool_endpoint,
                         pool_svc, pool_length, pool_token, pool_active,
                         rnd, gumbel, *, mesh, axis: str = "shard",
                         block_r: int = 256, fold: str | None = None,
                         interpret: bool | None = None) -> AdmitCommitResult:
    """``admit_commit`` sharded ``(R/M,)`` over mesh axis ``axis``.

    Same flat-array contract as ``route_match.admit_commit``; the pool is
    ``(I/M,)``-sharded over the axis (instance ``i`` lives on shard
    ``i // (I/M)``), the routing tables are replicated, and the result is
    bit-exact vs single-shard ``admit_commit`` on the same (concatenated)
    batch — see ``ref.admit_sharded_ref`` for the shard-major merge rule.
    Ragged batches pad to a multiple of the shard count with inert
    ``req_id = -1`` rows (an all-padding shard takes the ``lax.cond`` skip
    path).  Requires ``I % M == 0``.
    """
    M = mesh.shape[axis]
    I, C = pool_req_id.shape
    if I % M:
        raise ValueError(f"pool instances ({I}) must divide over the "
                         f"{M}-way mesh axis {axis!r}")
    R0, F = features.shape
    active_i32 = (pool_active != 0).astype(jnp.int32)
    pool = (pool_req_id.astype(jnp.int32), pool_endpoint.astype(jnp.int32),
            pool_svc.astype(jnp.int32), pool_length.astype(jnp.int32),
            pool_token.astype(jnp.int32))
    if R0 == 0:                          # empty batch: pool passes through
        z = jnp.zeros((0,), jnp.int32)
        zs = jnp.zeros_like(state.svc_rule_start)
        return AdmitCommitResult(
            z, z, z, z, z, state.ep_load,
            state.rr_cursor % jnp.maximum(state.cluster_ep_count, 1),
            zs, zs, jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32),
            state.aff_key, state.aff_ep,
            *pool, active_i32)
    R = -(-R0 // M) * M
    token = jnp.zeros((R0,), jnp.int32) if token is None else token
    R, req_id, svc, features, msg_bytes, rnd, gumbel, token = _rm._pad_rows(
        R, req_id, svc, features, msg_bytes, rnd, gumbel, token)
    R_loc = R // M
    fn = _build(mesh, axis, R_loc, min(block_r, R_loc), resolve_fold(fold),
                resolve_interpret(interpret))
    o = fn(req_id.astype(jnp.int32), svc.astype(jnp.int32), features,
           msg_bytes.astype(jnp.int32), token.astype(jnp.int32),
           rnd.astype(jnp.int32), gumbel.astype(jnp.float32), state,
           *pool, active_i32)
    return AdmitCommitResult(o[0][:R0], o[1][:R0], o[2][:R0], o[3][:R0],
                             o[4][:R0], *o[5:])


# --------------------------------------------------------------------------- #
# Sharded completion: the close path over an (I/M,)-sharded pool.
# --------------------------------------------------------------------------- #


def _complete_body(preq, pep, psvc, plen, ptok, pact, nxt, load0, rx0,
                   ewl0, ewt0, *, axis: str, eos: int, max_len: int,
                   block_i: int, fold: str, interpret: bool,
                   alpha_inflight: float, alpha_tput: float):
    """shard_map body: local fused completion with ZERO table bases so the
    kernel's (E,)/(S,) outputs are pure per-shard integer deltas, then one
    psum reconciles them against the replicated global bases.  The nonlinear
    f32 EWMA epilogue runs AFTER the psum, on the global integer counts —
    identical inputs to the single-shard kernel's in-kernel epilogue, so the
    accumulators are bit-exact for any shard count."""
    E, S = load0.shape[0], rx0.shape[0]
    res = _cp.complete(preq, pep, psvc, plen, ptok, pact, nxt,
                       jnp.zeros((E,), jnp.int32), jnp.zeros((S,), jnp.int32),
                       jnp.zeros((E,), jnp.float32),
                       jnp.zeros((E,), jnp.float32),
                       eos=eos, max_len=max_len, block_i=block_i, fold=fold,
                       interpret=interpret)
    cnt = jax.lax.psum(res.done_cnt, axis)                  # global releases
    ep_load = load0 - cnt
    rx = rx0 + jax.lax.psum(res.rx_bytes, axis)
    ewl, ewt = _cp.health_update(ewl0, ewt0, load0, cnt,
                                 alpha_inflight=alpha_inflight,
                                 alpha_tput=alpha_tput)
    return (res.req_id, res.endpoint, res.svc, res.length, res.token,
            res.active, res.done, ep_load, rx, cnt, ewl, ewt)


@lru_cache(maxsize=None)
def _build_complete(mesh, axis: str, eos: int, max_len: int, block_i: int,
                    fold: str, interpret: bool, alpha_inflight: float,
                    alpha_tput: float):
    """One compiled shard_map program per (mesh, axis, plan)."""
    from functools import partial

    from jax.sharding import PartitionSpec as P

    body = partial(_complete_body, axis=axis, eos=eos, max_len=max_len,
                   block_i=block_i, fold=fold, interpret=interpret,
                   alpha_inflight=alpha_inflight, alpha_tput=alpha_tput)
    sh = P(axis)
    rep = P()
    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=(sh,) * 7 + (rep,) * 4,
        out_specs=(sh,) * 7 + (rep,) * 5,
        check_vma=False)
    return jax.jit(f)


def complete_sharded(pool_req_id, pool_endpoint, pool_svc, pool_length,
                     pool_token, pool_active, nxt, ep_load, rx_bytes,
                     ep_inflight_ewma, ep_tput_ewma, *, mesh,
                     axis: str = "shard", eos: int, max_len: int,
                     block_i: int = 8, fold: str | None = None,
                     alpha_inflight: float = _cp.ALPHA_INFLIGHT,
                     alpha_tput: float = _cp.ALPHA_TPUT,
                     interpret: bool | None = None) -> CompleteResult:
    """``completion.complete`` over an ``(I/M,)``-sharded pool.

    Same flat-array contract; the (E,) load / EWMA tables and (S,) rx table
    are replicated, each shard folds its own pool slice, and one psum pass
    reconciles the integer counts before the shared ``health_update``
    epilogue — bit-exact vs single-shard ``complete`` on the whole pool.
    Requires ``I % M == 0``.
    """
    M = mesh.shape[axis]
    I, C = pool_req_id.shape
    if I % M:
        raise ValueError(f"pool instances ({I}) must divide over the "
                         f"{M}-way mesh axis {axis!r}")
    block_i = min(block_i, max(I // M, 1))
    fn = _build_complete(mesh, axis, eos, max_len, block_i,
                         resolve_fold(fold), resolve_interpret(interpret),
                         alpha_inflight, alpha_tput)
    o = fn(pool_req_id.astype(jnp.int32), pool_endpoint.astype(jnp.int32),
           pool_svc.astype(jnp.int32), pool_length.astype(jnp.int32),
           pool_token.astype(jnp.int32), (pool_active != 0).astype(jnp.int32),
           nxt.astype(jnp.int32), ep_load.astype(jnp.int32),
           rx_bytes.astype(jnp.int32),
           ep_inflight_ewma.astype(jnp.float32),
           ep_tput_ewma.astype(jnp.float32))
    return CompleteResult(*o)
