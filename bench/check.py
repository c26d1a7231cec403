"""The comparison that decides ``correct``, run after the window has
closed, the device's peak memory has been read and the program's state
has been dropped.

Numbers compared, each with its limit (``EXACT`` here; the tolerances,
which depend on the application, in the configuration file's
``"limits"``):

* ``table_mismatches``: entries of the routing tables the program held
  that differ from the configuration (exact: 0).
* ``tick_mismatches``: over the first tick of traffic and a seeded sample
  of the later ones, the outputs of ``serve_step`` (pool, load counters,
  rr cursors, affinity cache, PRNG key, flow metrics, done flags, the ids
  served, the float32 latency EWMAs) that differ from the reference's
  admission and completion replayed on the tick's own inputs (exact: 0).
* ``length_violations``: finished requests of the window whose length
  breaks the length rule (``max_len - 1`` tokens, or fewer ending on the
  end token) (exact: 0).
* ``unfinished``: requests of the run (the warm-up's too) neither
  finished nor dropped after the grace period (exact: 0).
* ``logit_gap``: over a seeded sample of the window's finished requests
  (the longest among them), the widest gap by which a served token's
  reference logit lies below the reference's best, the application's
  reference (``bench/apps/<name>.py``) run in float32 at the highest
  precision on the prompt and the served tokens.

The control (``control_numbers``; ``bench/run.py::execute`` with
``control=True``, read by ``bench/control.py``) puts the reference in the
program's place one precision below the one the configuration serves in
(``CONTROL``) and reads the same gap for the tokens it puts first; it has
to come out as not correct.
"""

from __future__ import annotations

import sys

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

EXACT = {"table_mismatches": 0, "tick_mismatches": 0,
         "length_violations": 0, "unfinished": 0}
TOLERANCES = ("logit_gap",)
DECODE_SAMPLE = 128          # finished requests the decode check reads
# the nearest precision below the one a configuration serves in
CONTROL = {"float32": jnp.bfloat16, "bfloat16": jnp.float8_e4m3fn}
POOL_FIELDS = ("req_id", "endpoint", "svc", "length", "token", "active")


def limits_for(config: dict) -> dict:
    return {**EXACT, **{k: config["limits"][k] for k in TOLERANCES}}


def _host(x) -> np.ndarray:
    return np.asarray(jax.device_get(x))


def state_view(st) -> dict:
    """The fields of an engine state the kernels read and write, on host."""
    r, m = st.routing, st.metrics
    out = {f"pool_{k}": _host(getattr(st.pool, k)).astype(np.int64)
           for k in POOL_FIELDS}
    out.update(
        key=_host(st.key), ep_load=_host(r.ep_load),
        rr_cursor=_host(r.rr_cursor), aff_key=_host(r.aff_key),
        aff_ep=_host(r.aff_ep), ep_inflight_ewma=_host(r.ep_inflight_ewma),
        ep_tput_ewma=_host(r.ep_tput_ewma), requests=_host(m.requests),
        tx_bytes=_host(m.tx_bytes), rx_bytes=_host(m.rx_bytes),
        no_route_match=_host(m.no_route_match), overflow=_host(m.overflow))
    return out


def routing_view(st) -> dict:
    return {k: _host(v) for k, v in st.routing._asdict().items()}


def replay_tick(tables, sample, *, eos: int, max_len: int) -> dict:
    """Mismatched entries, by field, of one recorded tick against the
    reference admission and completion on its inputs."""
    pre, reqs, post, out = sample
    before = state_view(pre)
    batch = {k: _host(v) for k, v in reqs._asdict().items()}
    mid = {**before, **reference.admit(tables, before, batch)}
    fin = reference.complete(mid, _host(out["emitted"]), eos=eos,
                             max_len=max_len)
    want = {**mid, **fin}
    got = state_view(post)
    got["done"] = _host(out["done"])
    got["served"] = _host(out["req_id"])
    want["served"] = mid["pool_req_id"]
    # the cluster of each request the program placed on another endpoint
    placed = (got["pool_active"] == 1) & (want["pool_active"] == 1) & (
        got["pool_req_id"] == want["pool_req_id"])
    moved = placed & (got["pool_endpoint"] != want["pool_endpoint"])
    bad = {}
    for e in want["pool_endpoint"][moved]:
        c = int(np.searchsorted(tables.ep_start, e, side="right") - 1)
        bad[f"endpoint@cluster{c}"] = bad.get(f"endpoint@cluster{c}", 0) + 1
    diag = dict(bad)
    bad = {}
    for k in (*(f"pool_{f}" for f in POOL_FIELDS), "key", "ep_load",
              "rr_cursor", "aff_key", "aff_ep", "requests", "tx_bytes",
              "rx_bytes", "no_route_match", "overflow", "done", "served",
              "ep_inflight_ewma", "ep_tput_ewma"):
        a, b = np.asarray(got[k]), np.asarray(want[k])
        if b.dtype.kind != "f":
            a, b = a.astype(np.int64), b.astype(np.int64)
        n = int(np.sum(a != b)) if a.shape == b.shape else b.size
        if n:
            bad[k] = n
    return bad, diag


def length_ok(tokens: list, eos: int, max_len: int) -> bool:
    n = len(tokens)
    if n == 0 or eos in tokens[:-1]:
        return False
    return n == max_len - 1 or (tokens[-1] == eos and n < max_len - 1)


def decode_sample(done: list, seed: int) -> list:
    """A seeded sample of finished requests, the longest among them."""
    if not done:
        return []
    g = np.random.default_rng([seed, 11])
    pick = set(g.choice(len(done), size=min(DECODE_SAMPLE, len(done)),
                        replace=False).tolist())
    pick.add(max(range(len(done)), key=lambda i: len(done[i].tokens)))
    return [done[i] for i in sorted(pick)]


def decode_batch(reqs: list, max_len: int) -> tuple[np.ndarray, np.ndarray]:
    """(inputs, served) (N, max_len - 1): the prompt then the served
    tokens as fed; the served tokens, -1 past each request's end."""
    S = max_len - 1
    inp = np.zeros((len(reqs), S), np.int32)
    srv = np.full((len(reqs), S), -1, np.int32)
    for n, r in enumerate(reqs):
        t = r.tokens[:S]
        srv[n, :len(t)] = t
        inp[n, 0] = r.prompt_token
        inp[n, 1:len(t)] = t[:-1]
    return inp, srv


def logit_gaps(ref, app: dict, params, reqs: list, max_len: int,
               dtype=jnp.float32) -> np.ndarray:
    """Gaps of the served tokens under the application's float32 reference
    ``ref`` (a ``bench/apps`` module); with a lower ``dtype`` the gaps of
    the tokens that precision puts first instead (the control, read at the
    same positions)."""
    inp, srv = decode_batch(reqs, max_len)
    logits = ref.forward_logits(app, params, jnp.asarray(inp))
    if dtype == jnp.float32:
        return reference.served_gaps(logits, srv)
    low = ref.forward_logits(app, params, jnp.asarray(inp), dtype)
    first = np.asarray(jnp.argmax(low, -1))
    return reference.served_gaps(logits, srv, first_choice=first)


def run_checks(dep, log, samples: list, done: list, seed: int) -> dict:
    """Every number compared, as ``{name: (value, limit)}``."""
    cfg, max_len = dep.config, dep.max_len
    eos = dep.loop.balancer.eos
    lim = limits_for(cfg)
    win = log.in_window()
    in_win = [r for r in done if win[r.req_id]]
    numbers = {}
    # the tables the program held (a sample is always taken on the first
    # tick of traffic)
    numbers["table_mismatches"] = (reference.table_mismatches(
        dep.tables, routing_view(samples[0][0])) if samples
        else dep.tables.ep_instance.size)
    by_field: dict = {}
    diag: dict = {}
    for s in samples:
        bad, d = replay_tick(dep.tables, s, eos=eos, max_len=max_len)
        for k, n in bad.items():
            by_field[k] = by_field.get(k, 0) + n
        for k, n in d.items():
            diag[k] = diag.get(k, 0) + n
    numbers["tick_mismatches"] = sum(by_field.values())
    if by_field:
        print(f"tick mismatches by field over {len(samples)} ticks: "
              f"{by_field}; requests placed elsewhere: {diag}",
              file=sys.stderr, flush=True)
    numbers["length_violations"] = sum(
        not length_ok(r.tokens, eos, max_len) for r in in_win)
    numbers["unfinished"] = int(np.sum(np.isnan(log.seen) & ~log.dropped))
    sample = decode_sample(in_win, seed)
    gaps = logit_gaps(dep.app_ref, cfg["application"], dep.params,
                      sample, max_len)
    numbers["logit_gap"] = float(gaps.max(initial=0.0))
    return {k: (v, lim[k]) for k, v in numbers.items()}


def control_numbers(dep, log, done: list, seed: int,
                    numbers: dict) -> tuple[dict, dict]:
    """The numbers compared with the control in the program's place: the
    tokens that the reference one precision below the served one
    (``CONTROL``) puts first, at each position of the same sampled prompts
    and served tokens, replace the served ones; every other number is the
    run's own.  Also returns what was read: tokens and wrong choices."""
    app = dep.config["application"]
    win = log.in_window()
    sample = decode_sample([r for r in done if win[r.req_id]], seed)
    gaps = logit_gaps(dep.app_ref, app, dep.params, sample, dep.max_len,
                      dtype=CONTROL[app["dtype"]])
    out = dict(numbers)
    out["logit_gap"] = (float(gaps.max(initial=0.0)),
                        numbers["logit_gap"][1])
    return out, {"tokens": int(sum(len(r.tokens) for r in sample)),
                 "wrong_tokens": int((gaps > 0).sum())}


def passed(numbers: dict) -> bool:
    return all(v <= lim for v, lim in numbers.values())
