"""Plain reference for the balancer's part of one XLB serving step,
independent of the program.

It restates, from the program's documented semantics, what ``serve_step``
must do, and imports nothing of ``repro``:

* ``RefTables``: the routing tables a configuration file describes (rule
  walk, cluster windows, policies, weights, Maglev rows), built here from
  the file and compared entry by entry with the tables the program holds.
* ``admit``: the admit-commit kernel as a sequential loop: first-match
  rule walk, policy pick with live counters, per-instance slot rank, pool
  commit, held release at the end of the batch, flow metrics.
* ``complete``: the completion kernel as a loop over the pool: done on
  EOS or on the length budget, load release, rx bytes, latency EWMAs.
* ``served_gaps``: how far each served token's logit lies below the best
  of the application's reference (``bench/apps/<name>.py``).

Array sizes are read from the arrays the step was given, never from the
program's constants.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

# The program's documented wire values.
POLICIES = {"rr": 0, "random": 1, "least_request": 2, "weighted": 3,
            "maglev": 4, "affinity": 5}
WILDCARD = -1
WINDOW = 64                  # endpoints scanned per cluster (gumbel width)
RX_BYTES_PER_TOKEN = 2
ALPHA_INFLIGHT = np.float32(0.25)
ALPHA_TPUT = np.float32(0.125)
HEADER_FIELDS = ("path", "user", "version", "tenant", "method",
                 "content-type", "region", "abtest")


def fnv1a(s: str) -> int:
    """31-bit FNV-1a of a header value (the ingress parse's hash)."""
    h = 0x811C9DC5
    for ch in s.encode():
        h = ((h ^ ch) * 0x01000193) & 0xFFFFFFFF
    return h & 0x7FFFFFFF


def features(headers: dict) -> np.ndarray:
    """The (8,) int32 feature row of one request's headers."""
    return np.array([fnv1a(headers[f]) if f in headers else 0
                     for f in HEADER_FIELDS], np.int32)


def flow_hash(feats: np.ndarray) -> np.ndarray:
    """31-bit FNV-style flow id over the feature columns, (..., F) → (...)."""
    f = np.asarray(feats).astype(np.uint32)
    h = np.full(f.shape[:-1], 0x811C9DC5, np.uint32)
    with np.errstate(over="ignore"):
        for j in range(f.shape[-1]):
            h = (h ^ f[..., j]) * np.uint32(0x01000193)
    return (h & np.uint32(0x7FFFFFFF)).astype(np.int64)


def _mix(x: int, salt: int) -> int:
    h = (int(x) ^ salt) & 0xFFFFFFFF
    h = (h * 0x01000193 + 0x811C9DC5) & 0xFFFFFFFF
    h ^= h >> 13
    h = (h * 0x5BD1E995) & 0xFFFFFFFF
    h ^= h >> 15
    return h


def maglev_row(ids: list, T: int) -> np.ndarray:
    """Canonical Maglev lookup row over endpoints keyed by instance id;
    entries are window offsets, -1 empty."""
    row = np.full((T,), -1, np.int64)
    if not ids:
        return row
    offset = [_mix(i, 0x9E3779B9) % T for i in ids]
    skip = [_mix(i, 0x85EBCA6B) % (T - 1) + 1 for i in ids]
    ptr = [0] * len(ids)
    filled = 0
    while filled < T:
        for k in range(len(ids)):
            while True:
                c = (offset[k] + ptr[k] * skip[k]) % T
                ptr[k] += 1
                if row[c] < 0:
                    row[c] = k
                    filled += 1
                    break
            if filled == T:
                break
    return row


def cluster_endpoints(cl: dict) -> tuple[list, list]:
    """(instance lanes, weights) of a configuration's cluster entry."""
    if "subsets" in cl:
        lanes, weights = [], []
        for sub in cl["subsets"]:
            n = len(sub["endpoints"])
            lanes += list(sub["endpoints"])
            weights += [float(sub["weight"]) / n] * n
        return lanes, weights
    if "lanes" in cl:
        lanes = list(range(cl["lanes"][0], cl["lanes"][0] + cl["lanes"][1]))
    else:
        lanes = list(cl["endpoints"])
    return lanes, list(cl.get("weights") or [1.0] * len(lanes))


@dataclasses.dataclass
class RefTables:
    """Routing tables of one configuration, packed as the program's build
    documents (clusters then rules in file order, contiguous from 0)."""

    rule_start: np.ndarray    # (services,)
    rule_count: np.ndarray
    rule_field: np.ndarray    # (rules,)
    rule_value: np.ndarray
    rule_cluster: np.ndarray
    ep_start: np.ndarray      # (clusters,)
    ep_count: np.ndarray
    policy: np.ndarray
    ep_instance: np.ndarray   # (endpoints,)
    ep_weight: np.ndarray     # (endpoints,) float32
    maglev: np.ndarray        # (clusters, T)

    @classmethod
    def build(cls, config: dict, maglev_width: int) -> "RefTables":
        cid = {c["name"]: i for i, c in enumerate(config["clusters"])}
        ep_start, ep_count, pol, inst, wts, mg = [], [], [], [], [], []
        for c in config["clusters"]:
            lanes, w = cluster_endpoints(c)
            ep_start.append(len(inst))
            ep_count.append(len(lanes))
            pol.append(POLICIES[c["policy"]])
            inst += lanes
            wts += w
            mg.append(maglev_row(lanes, maglev_width))
        rs, rc, rf, rv, rcl = [], [], [], [], []
        for s in config["services"]:
            rs.append(len(rf))
            rc.append(len(s["rules"]))
            for r in s["rules"]:
                rf.append(HEADER_FIELDS.index(r["header"]))
                rv.append(WILDCARD if r["value"] is None
                          else fnv1a(r["value"]))
                rcl.append(cid[r["cluster"]])
        a = lambda x: np.asarray(x, np.int64)
        return cls(a(rs), a(rc), a(rf), a(rv), a(rcl), a(ep_start),
                   a(ep_count), a(pol), a(inst),
                   np.asarray(wts, np.float32), np.stack(mg))


def table_mismatches(t: RefTables, routing: dict) -> int:
    """Entries of the program's tables (a name → array dict) that differ
    from the configuration's: every rule, cluster window, policy, endpoint
    and Maglev row the configuration defines, plus any endpoint marked
    drained (the configurations drain none)."""
    g = {k: np.asarray(v) for k, v in routing.items()}
    S, C, R, E = len(t.rule_start), len(t.ep_start), len(t.rule_field), \
        len(t.ep_instance)
    pairs = [
        (g["svc_rule_start"][:S], t.rule_start),
        (g["svc_rule_count"][:S], t.rule_count),
        (g["rule_field"][:R], t.rule_field),
        (g["rule_value"][:R], t.rule_value),
        (g["rule_cluster"][:R], t.rule_cluster),
        (g["cluster_ep_start"][:C], t.ep_start),
        (g["cluster_ep_count"][:C], t.ep_count),
        (g["cluster_policy"][:C], t.policy),
        (g["ep_instance"][:E], t.ep_instance),
        (g["ep_weight"][:E], t.ep_weight),
        (g["maglev_table"][:C], t.maglev),
    ]
    bad = sum(int(np.sum(a != b)) if a.shape == b.shape else b.size
              for a, b in pairs)
    return bad + int(np.sum(g["ep_drained"] != 0))


# --------------------------------------------------------------------------- #
# the admit-commit kernel
# --------------------------------------------------------------------------- #


@functools.partial(jax.jit, static_argnums=1)
def draws(key, R: int):
    """The engine's per-batch draws from its key: (next key, rnd, gumbel),
    compiled as the engine compiles them (a transcendental evaluated
    outside a compiled program may round differently on the chip)."""
    key, sub = jax.random.split(key)
    kr, kw, _ = jax.random.split(sub, 3)
    rnd = jax.random.randint(kr, (R,), 0, 1 << 30, dtype=jnp.int32)
    gum = jax.random.gumbel(kw, (R, WINDOW), jnp.float32)
    return key, rnd, gum


def admit(t: RefTables, state: dict, reqs: dict) -> dict:
    """One admission batch against ``state`` (host numpy copies of the
    live counters and pool: ep_load, rr_cursor, aff_key, aff_ep, pool_*,
    key, metrics).  Returns the new values of every field it writes."""
    rid = np.asarray(reqs["req_id"], np.int64)
    R = rid.shape[0]
    if not (rid >= 0).any():               # admission is skipped entirely
        return {}
    key, rnd, gum = draws(jnp.asarray(state["key"]), R)
    rnd = np.asarray(rnd, np.int64)
    svc = np.asarray(reqs["svc"], np.int64)
    feats = np.asarray(reqs["features"], np.int64)
    mb = np.asarray(reqs["msg_bytes"], np.int64)
    tok = np.asarray(reqs["token"], np.int64)
    fkey = flow_hash(np.asarray(reqs["features"], np.int32))
    loads = np.asarray(state["ep_load"], np.int64).copy()
    cur = np.asarray(state["rr_cursor"], np.int64).copy()
    affk = np.asarray(state["aff_key"], np.int64).copy()
    affe = np.asarray(state["aff_ep"], np.int64).copy()
    A = affk.shape[0]
    pool = {k: np.asarray(state[f"pool_{k}"], np.int64).copy()
            for k in ("req_id", "endpoint", "svc", "length", "token",
                      "active")}
    free = pool["active"] == 0
    n_svc = np.asarray(state["requests"]).shape[0]
    T = t.maglev.shape[1]
    icnt = np.zeros(free.shape[0], np.int64)
    sreq = np.zeros(n_svc, np.int64)
    stx = np.zeros(n_svc, np.int64)
    no_route = held = 0
    held_eps = []
    n_clusters = len(t.ep_start)
    gum = np.asarray(gum, np.float32)
    # the kernel's f32 log of the weights, on the same backend
    logw = np.asarray(jnp.log(jnp.asarray(t.ep_weight, jnp.float32) + 1e-9))

    def maglev_pick(r, c, elig):
        off = int(t.maglev[c, fkey[r] % T])
        if 0 <= off < t.ep_count[c]:
            return int(t.ep_start[c]) + off
        return elig[fkey[r] % len(elig)]

    for r in range(R):
        if rid[r] < 0:
            continue
        c = -1
        if 0 <= svc[r] < len(t.rule_start):
            s0 = t.rule_start[svc[r]]
            for j in range(s0, s0 + t.rule_count[svc[r]]):
                if t.rule_value[j] in (WILDCARD, feats[r, t.rule_field[j]]):
                    c = int(t.rule_cluster[j])
                    break
        if c < 0:
            no_route += 1
            continue
        elig = [int(t.ep_start[c]) + j
                for j in range(min(int(t.ep_count[c]), WINDOW))]
        if not elig:
            continue
        pol = int(t.policy[c])
        if pol == POLICIES["rr"]:
            ep = elig[cur[c] % len(elig)]
        elif pol == POLICIES["random"]:
            ep = elig[rnd[r] % len(elig)]
        elif pol == POLICIES["least_request"]:
            ep = elig[int(np.argmin([loads[e] for e in elig]))]
        elif pol == POLICIES["weighted"]:
            ep = elig[int(np.argmax(logw[elig] + gum[r, :len(elig)]))]
        elif pol == POLICIES["maglev"]:
            ep = maglev_pick(r, c, elig)
        elif pol == POLICIES["affinity"]:
            sl = fkey[r] % A
            lo, hi = t.ep_start[c], t.ep_start[c] + t.ep_count[c]
            if affk[sl] == fkey[r] and lo <= affe[sl] < hi:
                ep = int(affe[sl])
            else:
                ep = maglev_pick(r, c, elig)
                if affk[sl] in (-1, fkey[r]):
                    affk[sl], affe[sl] = fkey[r], ep
        else:
            raise ValueError(f"policy {pol} has no reference")
        cur[c] += 1
        loads[ep] += 1
        inst = int(t.ep_instance[ep])
        rank = icnt[inst]
        icnt[inst] += 1
        slots = np.flatnonzero(free[inst])
        if rank < slots.shape[0]:
            s = slots[rank]
            for k, v in (("req_id", rid[r]), ("endpoint", ep),
                         ("svc", svc[r]), ("length", 0), ("token", tok[r]),
                         ("active", 1)):
                pool[k][inst, s] = v
            if svc[r] < n_svc:
                sreq[svc[r]] += 1
                stx[svc[r]] += mb[r]
        else:
            held += 1
            held_eps.append(ep)
    for e in held_eps:
        loads[e] -= 1
    cur[:n_clusters] %= np.maximum(t.ep_count, 1)
    cur[n_clusters:] = 0
    out = {f"pool_{k}": v for k, v in pool.items()}
    out.update(
        key=key, ep_load=loads, rr_cursor=cur, aff_key=affk, aff_ep=affe,
        requests=np.asarray(state["requests"], np.int64) + sreq,
        tx_bytes=np.asarray(state["tx_bytes"], np.int64) + stx,
        no_route_match=int(state["no_route_match"]) + no_route,
        overflow=int(state["overflow"]) + held)
    return out


# --------------------------------------------------------------------------- #
# the completion kernel
# --------------------------------------------------------------------------- #


def ewma(prev: np.ndarray, obs: np.ndarray, alpha: np.float32
         ) -> np.ndarray:
    """``prev + alpha * (obs - prev)`` in float32, each step rounded as the
    backend rounds it: a TPU flushes subnormal results to zero, so an idle
    endpoint's EWMA stops decaying near the smallest normal number."""
    ftz = jax.default_backend() == "tpu"

    def f32(x):
        x = np.asarray(x, np.float32)
        if ftz:
            x = np.where(np.abs(x) < np.finfo(np.float32).tiny,
                         np.float32(0), x)
        return x

    return f32(prev + f32(alpha * f32(obs - prev)))


def complete(state: dict, nxt: np.ndarray, *, eos: int, max_len: int
             ) -> dict:
    """One completion pass over the pool after a decode that emitted
    ``nxt`` (I, C); ``state`` holds the pool and counters after admission."""
    pool = {k: np.asarray(state[f"pool_{k}"], np.int64).copy()
            for k in ("req_id", "endpoint", "svc", "length", "token",
                      "active")}
    nx = np.asarray(nxt, np.int64)
    loads = np.asarray(state["ep_load"], np.int64).copy()
    occ = loads.astype(np.float32)          # in flight during the step
    rx = np.asarray(state["rx_bytes"], np.int64).copy()
    E, S = loads.shape[0], rx.shape[0]
    cnt = np.zeros(E, np.int64)
    done = np.zeros(pool["active"].shape, np.int64)
    for i, c in zip(*np.nonzero(pool["active"])):
        sv = max(int(pool["svc"][i, c]), 0)
        if sv < S:
            rx[sv] += RX_BYTES_PER_TOKEN
        pool["length"][i, c] += 1
        pool["token"][i, c] = nx[i, c]
        if nx[i, c] == eos or pool["length"][i, c] >= max_len - 1:
            done[i, c] = 1
            ep = int(pool["endpoint"][i, c])
            if 0 <= ep < E:
                loads[ep] -= 1
                cnt[ep] += 1
            pool["req_id"][i, c] = -1
            pool["endpoint"][i, c] = -1
            pool["length"][i, c] = 0
            pool["active"][i, c] = 0
    ewl = np.asarray(state["ep_inflight_ewma"], np.float32)
    ewt = np.asarray(state["ep_tput_ewma"], np.float32)
    cnt = cnt.astype(np.float32)
    out = {f"pool_{k}": v for k, v in pool.items()}
    out.update(
        done=done, ep_load=loads, rx_bytes=rx,
        ep_inflight_ewma=ewma(ewl, occ, ALPHA_INFLIGHT),
        ep_tput_ewma=ewma(ewt, cnt, ALPHA_TPUT))
    return out


def served_gaps(logits, served, first_choice=None) -> np.ndarray:
    """Per-token gap by which a served token's reference logit lies below
    the reference's best.  ``logits`` (N, S, V) from ``forward_logits`` on
    the prompt followed by the served tokens, ``served`` (N, S) int with -1
    past a sequence's end.  With ``first_choice`` (N, S) the gap is read
    for those tokens instead (the control's own first choices)."""
    lg = np.asarray(logits, np.float64)
    tok = np.asarray(served if first_choice is None else first_choice)
    valid = np.asarray(served) >= 0
    got = np.take_along_axis(lg, np.maximum(tok, 0)[..., None], -1)[..., 0]
    return np.where(valid, lg.max(-1) - got, 0.0)
