"""Serving launcher: ``python -m repro.launch.serve --engine
xlb|istio|cilium [...]``.

Boots the selected serving engine (the XLB in-graph datapath or either
sidecar baseline — all behind the one Balancer protocol) with the selected
application model, builds routing through a ControlPlane, and drives a
synthetic request stream through the continuous-batching loop.

The defaults are the paper's fleet: 50 instances of ``xlb-service-model``
at its registered width, 8 decode slots each, admission batches of 256 and
1024 requests.  Every ``--arch`` is served at its registered width.
"""

from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.core.balancer import ENGINE_KINDS, make_balancer
from repro.core.control import ControlPlane
from repro.core.routing_table import (POLICY_NAMES, Cluster, Rule,
                                      ServiceConfig)
from repro.launch.cache import setup_compile_cache
from repro.models import model as M
from repro.runtime.serve_loop import DrainReport, Request, ServeLoop

SERVICE_MODEL = "xlb-service-model"


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=SERVICE_MODEL,
                    choices=ASSIGNED_ARCHS + [SERVICE_MODEL])
    ap.add_argument("--engine", default="xlb", choices=ENGINE_KINDS)
    ap.add_argument("--instances", type=int, default=50)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--admit-batch", type=int, default=256,
                    help="requests per admission batch (one fused admit "
                    "kernel launch per engine tick)")
    ap.add_argument("--requests", type=int, default=1024)
    ap.add_argument("--max-len", type=int, default=24)
    ap.add_argument("--policy", default="least_request",
                    choices=sorted(POLICY_NAMES),
                    help="load-balancing policy for the serving cluster "
                    "(the registry in core/policy_defs.py)")
    ap.add_argument("--shards", type=int, default=1,
                    help="shard the admission batch + pool over an M-way "
                    "mesh axis (xlb engine only; needs M devices — off-TPU "
                    "set XLA_FLAGS=--xla_force_host_platform_device_count)")
    return ap


class ServeRun(NamedTuple):
    """One drained serving run: the loop (live engine state included), its
    drain report and the wall seconds of submit + drain."""

    loop: ServeLoop
    report: DrainReport
    wall: float


def build(args: argparse.Namespace) -> tuple:
    """The fleet the arguments describe: (application config, ServeLoop)
    with the engine booted and routing attached through a ControlPlane."""
    cfg = get_config(args.arch)
    if cfg.is_encdec:
        raise SystemExit("enc-dec serving needs prompt frames; use the "
                         "dry-run decode cells for whisper")
    params = M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
    cp = ControlPlane(
        [ServiceConfig("svc", rules=[Rule(0, None, "pool")])],
        [Cluster("pool", endpoints=list(range(args.instances)),
                 policy=POLICY_NAMES[args.policy])])
    kw = {}
    if args.shards > 1:
        if args.engine != "xlb":
            raise SystemExit("--shards needs the in-graph engine "
                             "(--engine xlb); the sidecar baselines route "
                             "on the host")
        if args.instances % args.shards:
            raise SystemExit(f"--instances {args.instances} must divide "
                             f"over --shards {args.shards}")
        from repro.launch.mesh import make_shard_mesh
        kw = dict(shards=args.shards,
                  shard_mesh=make_shard_mesh(args.shards))
    eng = make_balancer(args.engine, cfg, args.instances, args.slots,
                        args.max_len, **kw)
    return cfg, ServeLoop(eng, params, cp, admit_batch=args.admit_batch,
                          dtype=jnp.float32)


def drive(cfg, loop: ServeLoop, n_requests: int) -> ServeRun:
    """Submit ``n_requests`` synthetic requests and drain the loop."""
    t0 = time.perf_counter()
    for i in range(n_requests):
        loop.submit(Request(req_id=i, service=0,
                            headers={"path": f"/api/{i % 4}"},
                            prompt_token=3 + i % (cfg.vocab - 3)))
    rep = loop.drain()
    return ServeRun(loop, rep, time.perf_counter() - t0)


def main(argv=None) -> int:
    setup_compile_cache()
    args = build_parser().parse_args(argv)
    cfg, loop = build(args)
    run = drive(cfg, loop, args.requests)
    rep, wall = run.report, run.wall
    lat = [r.t_done - r.t_submit for r in rep.done] or [float("nan")]
    print(f"{cfg.name} [{args.engine}] {args.instances} instances x "
          f"{args.slots} slots, admit batch {args.admit_batch}: "
          f"{len(rep.done)} requests in {wall:.2f}s "
          f"({len(rep.done)/wall:.1f} req/s), avg latency "
          f"{1e3*np.mean(lat):.1f} ms, p99 {1e3*np.percentile(lat, 99):.1f} ms")
    if rep.queued or rep.inflight or rep.dropped:
        print(f"drain left: queued={rep.queued} inflight={rep.inflight} "
              f"dropped={len(rep.dropped)}")
    m = loop.state.metrics
    print(f"metrics: tx={int(m.tx_bytes.sum())}B rx={int(m.rx_bytes.sum())}B "
          f"no_route={int(m.no_route_match)} overflow={int(m.overflow)}")
    return len(rep.done)


if __name__ == "__main__":
    main()
