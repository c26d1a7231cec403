#!/usr/bin/env python3
"""Smoke run of the XLB serving path on a TPU.

    python chip_smoke.py               # one chip, phases a-e below
    python chip_smoke.py --four-chips  # the sharded path on four chips

One chip, in one process:

  a. device   — ``jax.devices()[0].platform`` must be ``tpu``; on any other
                backend the run exits non-zero and prints no result.
  b. fleet    — ``launch/serve.py``'s own code serves the paper's fleet on
                the xlb engine: 50 instances of ``xlb-service-model`` at
                its registered width, 8 slots each, admission batches of
                256, 1024 requests.  Every request completes; nothing is
                dropped, queued or in flight at drain; ``no_route = 0``.
  c. compiled — the lowered ``serve_step`` holds one ``tpu_custom_call``
                for the admit-commit kernel and one for the completion
                kernel (no interpret mode, no jnp stand-in).
  d. oracle   — one admission batch of 256 over the 50 × 8 pool, on a
                routing state with a cluster per registered policy,
                drained endpoints and preloaded counters, and one
                completion over the result, against ``admit_commit_ref`` /
                ``complete_ref``: integer outputs bit-exact, the f32
                latency EWMAs within 1e-6 relative.
  e. sidecars — the same request count completes on ``istio`` and
                ``cilium``.

``--four-chips`` runs only the sharded path and what it is compared with:
``Engine(shards=4)`` serving a 52-instance fleet to completion, and
``admit_commit_sharded`` / ``complete_sharded`` against single-shard
``admit_commit`` / ``complete`` on the same batch (integer outputs bit for
bit, the shard-major merge rule of ``ref.admit_sharded_ref``), with the
pool outputs spread over the four devices.

Timings and compile seconds go on earlier lines; the last stdout line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
A failed phase prints its traceback and the run goes on to the next, so
one run reports every fault; any failure exits non-zero without that
line.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

FLEET = dict(instances=50, slots=8, admit_batch=256, requests=1024,
             max_len=24)
SHARDS, SHARDED_INSTANCES = 4, 52     # smallest multiple of 4 at or above 50
EOS = 1
EWMA_RTOL = 1e-6


def log(msg: str) -> None:
    print(msg, flush=True)


def _serve_args(engine: str, *, instances: int, slots: int,
                admit_batch: int, requests: int, max_len: int,
                shards: int = 1):
    from repro.launch import serve
    return serve.build_parser().parse_args([
        "--engine", engine, "--instances", str(instances),
        "--slots", str(slots), "--admit-batch", str(admit_batch),
        "--requests", str(requests), "--max-len", str(max_len),
        "--shards", str(shards)])


def check_served(run, n_requests: int, label: str) -> None:
    """Phase b/e gate: every request done, nothing stranded, no NO_ROUTE."""
    rep, m = run.report, run.loop.state.metrics
    no_route = int(m.no_route_match)
    if (len(rep.done) != n_requests or rep.dropped or rep.queued
            or rep.inflight or no_route):
        raise AssertionError(
            f"{label}: done {len(rep.done)}/{n_requests}, dropped "
            f"{len(rep.dropped)}, queued {rep.queued}, inflight "
            f"{rep.inflight}, no_route {no_route}")
    log(f"{label}: {len(rep.done)}/{n_requests} requests in "
        f"{run.loop.ticks} ticks, {run.wall:.2f}s wall (compile included), "
        f"dropped 0, queued 0, inflight 0, no_route 0")


def serve_engine(engine: str, **fleet):
    """Serve one fleet through ``launch/serve.py``'s build/drive code."""
    from repro.launch import serve
    args = _serve_args(engine, **fleet)
    cfg, loop = serve.build(args)
    run = serve.drive(cfg, loop, args.requests)
    check_served(run, args.requests,
                 f"{engine} {args.instances}x{args.slots} "
                 f"batch {args.admit_batch}")
    return run


def check_compiled(loop) -> float:
    """Phase c: both datapath kernels are Mosaic custom calls inside the
    lowered serve_step.  Returns the compile seconds of serve_step."""
    import jax
    import jax.numpy as jnp

    from repro.core.balancer import RequestBatch
    from repro.core.routing_table import N_FEATURES
    R = loop.admit_batch
    z = jnp.zeros((R,), jnp.int32)
    reqs = RequestBatch(req_id=z - 1, svc=z,
                        features=jnp.zeros((R, N_FEATURES), jnp.int32),
                        token=z, msg_bytes=z)
    t0 = time.perf_counter()
    lowered = loop.serve_step.lower(loop.params, loop.state, reqs)
    text = lowered.as_text()
    lowered.compile()
    secs = time.perf_counter() - t0
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for kernel in ("xlb_admit_commit", "xlb_complete"):
        n = sum(kernel in ln for ln in calls)
        if n != 1:
            raise AssertionError(f"serve_step lowers {n} tpu_custom_call(s) "
                                 f"for {kernel}, want 1")
    log(f"compiled: serve_step holds tpu_custom_call xlb_admit_commit and "
        f"xlb_complete ({len(calls)} custom calls), lower+compile "
        f"{secs:.2f}s")
    return secs


# --------------------------------------------------------------------------- #
# oracle inputs: a fleet-sized routing state and one admission batch
# --------------------------------------------------------------------------- #


def fleet_state(n_instances: int, seed: int):
    """A routing state over the fleet with one cluster per registered policy
    (each spanning every instance), one rule each keyed on feature 0,
    preloaded load counters and rr cursors, and a few drained endpoints
    whose Maglev rows were not rebuilt (the defensive fallback path)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import policy_defs
    from repro.core.routing_table import (POLICY_WEIGHTED, Cluster, Rule,
                                          ServiceConfig, build_state)
    rng = np.random.default_rng(seed)
    clusters, rules = [], []
    for p in policy_defs.REGISTRY:
        w = (rng.uniform(0.1, 4.0, n_instances).tolist()
             if p.enum == POLICY_WEIGHTED else None)
        clusters.append(Cluster(f"c_{p.name}",
                                endpoints=list(range(n_instances)),
                                policy=p.enum, weights=w))
        rules.append(Rule(0, str(p.enum), f"c_{p.name}"))
    st, _ = build_state([ServiceConfig("svc", rules=rules),
                         ServiceConfig("norules", rules=[])], clusters)
    n_ep = n_instances * len(clusters)
    load = np.zeros(st.ep_load.shape, np.int32)
    load[:n_ep] = rng.integers(0, 4, n_ep)
    drained = np.zeros(st.ep_drained.shape, np.int32)
    drained[rng.choice(n_ep, size=max(3, n_ep // 40), replace=False)] = 1
    cur = np.zeros(st.rr_cursor.shape, np.int32)
    cur[:len(clusters)] = rng.integers(0, n_instances, len(clusters))
    return st._replace(ep_load=jnp.asarray(load),
                       ep_drained=jnp.asarray(drained),
                       rr_cursor=jnp.asarray(cur))


def fleet_batch(R: int, n_instances: int, slots: int, seed: int):
    """(RequestBatch, pool, rnd, gumbel): padding rows, a service without
    rules, unmatched headers, repeated flows (affinity hits) and a pool
    about half full (so some requests are held)."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core import policy_defs
    from repro.core.balancer import PoolState, RequestBatch
    from repro.core.routing_table import (MAX_EPS_PER_CLUSTER, N_FEATURES,
                                          fnv1a)
    rng = np.random.default_rng(seed)
    rid = np.where(rng.random(R) < 0.9, np.arange(R), -1).astype(np.int32)
    svc = np.where(rng.random(R) < 0.95, 0, 1).astype(np.int32)
    enums = rng.integers(0, len(policy_defs.REGISTRY), R)
    feats = rng.integers(0, 1 << 30, (R, N_FEATURES)).astype(np.int32)
    feats[:, 0] = np.where(rng.random(R) < 0.95,
                           [fnv1a(str(e)) for e in enums], 7)
    # one flow repeated through the batch, routed to the affinity cluster:
    # its first admission fills the cache, the later ones hit it
    rid[0], svc[0] = 0, 0
    feats[0, 0] = fnv1a(str(policy_defs.POLICY_AFFINITY))
    feats[1::5] = feats[0]
    svc[1::5] = svc[0]
    reqs = RequestBatch(
        req_id=jnp.asarray(rid), svc=jnp.asarray(svc),
        features=jnp.asarray(feats),
        token=jnp.asarray(rng.integers(2, 500, R).astype(np.int32)),
        msg_bytes=jnp.asarray(rng.integers(1, 500, R).astype(np.int32)))
    act = rng.random((n_instances, slots)) < 0.5
    pool = PoolState(
        jnp.asarray(np.where(act, 10_000 + np.arange(act.size).reshape(
            act.shape), -1).astype(np.int32)),
        jnp.asarray(np.where(act, rng.integers(0, n_instances, act.shape),
                             -1).astype(np.int32)),
        jnp.zeros(act.shape, jnp.int32),
        jnp.asarray(np.where(act, rng.integers(0, 20, act.shape), 0)
                    .astype(np.int32)),
        jnp.zeros(act.shape, jnp.int32), jnp.asarray(act))
    rnd = jnp.asarray(rng.integers(0, 1 << 30, R).astype(np.int32))
    gum = jnp.asarray(rng.gumbel(size=(R, MAX_EPS_PER_CLUSTER))
                      .astype(np.float32))
    return reqs, pool, rnd, gum


def completion_inputs(pool, ep_load, seed: int):
    """(nxt, rx_bytes, inflight_ewma, tput_ewma) for one completion step:
    about a third of the active slots emit EOS."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.routing_table import MAX_SERVICES
    rng = np.random.default_rng(seed)
    shape = pool.req_id.shape
    nxt = np.where(rng.random(shape) < 0.3, EOS,
                   rng.integers(2, 500, shape)).astype(np.int32)
    E = ep_load.shape[0]
    return (jnp.asarray(nxt),
            jnp.asarray(rng.integers(0, 100, MAX_SERVICES).astype(np.int32)),
            jnp.asarray(rng.uniform(0, 5, E).astype(np.float32)),
            jnp.asarray(rng.uniform(0, 2, E).astype(np.float32)))


def _same(label: str, want, got) -> None:
    import numpy as np
    w = np.asarray(want)
    g = np.asarray(got).astype(w.dtype)
    if w.shape != g.shape or not np.array_equal(w, g):
        bad = int((w != g).sum()) if w.shape == g.shape else -1
        raise AssertionError(f"{label}: {bad} mismatched entries "
                             f"(shape {w.shape} vs {g.shape})")


def _close(label: str, want, got, rtol: float) -> None:
    import numpy as np
    w, g = np.asarray(want, np.float64), np.asarray(got, np.float64)
    err = np.abs(w - g) / np.maximum(np.abs(w), 1e-30)
    if w.shape != g.shape or float(err.max(initial=0.0)) > rtol:
        raise AssertionError(f"{label}: max relative error "
                             f"{float(err.max(initial=0.0)):.3g} > {rtol}")


def _flat(res) -> list:
    """(name, array) of a kernel or oracle result in field order, a
    ``pool`` field expanded in place — the oracles' flat results and the
    ops wrappers' pooled ones then line up field by field."""
    out = []
    for name, v in zip(res._fields, res):
        out += ([(f"pool.{k}", x) for k, x in v._asdict().items()]
                if name == "pool" else [(name, v)])
    return out


def _compare(label: str, want, got) -> None:
    """Integer fields bit for bit, f32 fields (the EWMAs) within
    ``EWMA_RTOL`` relative."""
    import numpy as np
    w, g = _flat(want), _flat(got)
    if len(w) != len(g):
        raise AssertionError(f"{label}: {len(w)} fields vs {len(g)}")
    for (name, a), (_, b) in zip(w, g):
        if np.asarray(a).dtype.kind == "f":
            _close(f"{label} {name}", a, b, EWMA_RTOL)
        else:
            _same(f"{label} {name}", a, b)


def check_oracle(n_instances: int, slots: int, batch: int, *,
                 max_len: int = 24, seed: int = 0) -> None:
    """Phase d: the fused kernels against the sequential oracles."""
    from repro.kernels import ops, ref
    st = fleet_state(n_instances, seed)
    reqs, pool, rnd, gum = fleet_batch(batch, n_instances, slots, seed + 1)
    got = ops.admit_commit(reqs, st, pool, rnd, gum)
    want = ref.admit_commit_ref(reqs.req_id, reqs.svc, reqs.features,
                                reqs.msg_bytes, reqs.token, st, *pool, rnd,
                                gum)
    _compare("admit_commit vs admit_commit_ref", want, got)
    nxt, rx, ewl, ewt = completion_inputs(got.pool, got.ep_load, seed + 2)
    cgot = ops.complete(got.pool, nxt, got.ep_load, rx, ewl, ewt, eos=EOS,
                        max_len=max_len)
    cwant = ref.complete_ref(*got.pool, nxt, got.ep_load, rx, ewl, ewt,
                             eos=EOS, max_len=max_len)
    _compare("complete vs complete_ref", cwant, cgot)
    log(f"oracle: admit_commit over {batch} requests x {n_instances}x{slots} "
        f"pool matches admit_commit_ref bit for bit (admitted "
        f"{int(got.ok.sum())}, held {int(got.held)}, no_route "
        f"{int(got.no_route)}); complete matches complete_ref (done "
        f"{int(cgot.done.sum())}), EWMAs within {EWMA_RTOL} relative")


def check_sharded(n_instances: int, slots: int, batch: int, shards: int, *,
                  max_len: int = 24, seed: int = 0) -> None:
    """Four-chip comparison: the sharded admit/complete against the
    single-shard kernels on the same batch, and the pool spread over the
    mesh."""
    from repro.kernels import ops
    from repro.launch.mesh import make_shard_mesh
    mesh = make_shard_mesh(shards)
    st = fleet_state(n_instances, seed)
    reqs, pool, rnd, gum = fleet_batch(batch, n_instances, slots, seed + 1)
    want = ops.admit_commit(reqs, st, pool, rnd, gum)
    got = ops.admit_commit_sharded(reqs, st, pool, rnd, gum, mesh=mesh)
    _compare(f"admit_commit_sharded x{shards} vs admit_commit", want, got)
    nxt, rx, ewl, ewt = completion_inputs(want.pool, want.ep_load, seed + 2)
    cwant = ops.complete(want.pool, nxt, want.ep_load, rx, ewl, ewt,
                         eos=EOS, max_len=max_len)
    cgot = ops.complete_sharded(want.pool, nxt, want.ep_load, rx, ewl, ewt,
                                mesh=mesh, eos=EOS, max_len=max_len)
    _compare(f"complete_sharded x{shards} vs complete", cwant, cgot)
    for label, arr in (("admit pool", got.pool.req_id),
                       ("complete pool", cgot.pool.req_id)):
        devs = {s.device for s in arr.addressable_shards}
        rows = {s.data.shape[0] for s in arr.addressable_shards}
        if len(devs) != shards or rows != {n_instances // shards}:
            raise AssertionError(f"{label} lives on {len(devs)} device(s) "
                                 f"in row blocks {sorted(rows)}, want "
                                 f"{shards} blocks of "
                                 f"{n_instances // shards}")
    log(f"sharded: admit_commit_sharded and complete_sharded over {shards} "
        f"devices match the single-shard kernels on {batch} requests x "
        f"{n_instances}x{slots} (integers bit for bit, EWMAs within "
        f"{EWMA_RTOL}); pools split {shards} ways, "
        f"{n_instances // shards} instances per device")


# --------------------------------------------------------------------------- #


def run_phases(phases) -> list:
    """Run every (name, fn) phase even after one fails, so one run reports
    every fault; returns the names of the phases that raised."""
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        log(f"phase {name}: {'FAILED' if name in failed else 'ok'} in "
            f"{time.perf_counter() - t0:.2f}s")
    return failed


def fleet_phase() -> None:
    """Phases b and c: the compiled serve_step, then the fleet served."""
    from repro.launch import serve
    args = _serve_args("xlb", **FLEET)
    cfg, loop = serve.build(args)
    check_compiled(loop)
    run = serve.drive(cfg, loop, args.requests)
    check_served(run, args.requests, f"fleet: xlb {args.instances}x"
                 f"{args.slots} batch {args.admit_batch}")


def one_chip() -> list:
    return run_phases([
        ("fleet+compiled", fleet_phase),
        ("oracle", lambda: check_oracle(
            FLEET["instances"], FLEET["slots"], FLEET["admit_batch"],
            max_len=FLEET["max_len"])),
        ("istio", lambda: serve_engine("istio", **FLEET)),
        ("cilium", lambda: serve_engine("cilium", **FLEET)),
    ])


def four_chips() -> list:
    fleet = dict(FLEET, instances=SHARDED_INSTANCES)
    return run_phases([
        ("sharded fleet", lambda: serve_engine("xlb", shards=SHARDS,
                                               **fleet)),
        ("sharded vs single", lambda: check_sharded(
            SHARDED_INSTANCES, FLEET["slots"], FLEET["admit_batch"], SHARDS,
            max_len=FLEET["max_len"])),
    ])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded path on 4 devices and the "
                    "single-shard path it is compared with")
    args = ap.parse_args(argv)
    try:
        from repro.launch.cache import setup_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repo's sources are not next to this "
              f"script ({e})", file=sys.stderr)
        return 2
    cache = setup_compile_cache()
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU — JAX's first device is "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    want = SHARDS if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: --four-chips needs {SHARDS} devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 1
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache}")
    t0 = time.perf_counter()
    failed = four_chips() if args.four_chips else one_chip()
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    log(f"all phases done in {time.perf_counter() - t0:.2f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
