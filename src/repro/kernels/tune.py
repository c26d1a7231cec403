"""Block-size autotuner for the datapath kernels (admit / completion).

The right tile shape for the fused Pallas programs is backend- and
shape-dependent: under the CPU interpreter the grid is a sequential loop, so
small tiles multiply per-op dispatch overhead while huge tiles blow up the
per-tile intermediates (the least-request water-fill is O(BR·WE·log BR));
on a real TPU the trade is VMEM footprint vs pipeline occupancy.  Rather
than hard-coding one ``block_r``, the ops wrappers ask this module for a
plan at first use: the sweep times the actual kernel on synthetic
shape-matched inputs for a handful of candidate tile sizes, picks the
fastest, and caches the choice per (kernel, backend, shape) for the life of
the process.  Everything flows through ``kernels/ops.py``'s
``static_argnames`` seam, so a plan is just a pair of compile-time
constants.

Environment overrides (CI determinism — a pinned run never sweeps):

  ``XLB_AUTOTUNE=0``   disable sweeping entirely: heuristic defaults
  ``XLB_BLOCK_R=n``    pin the admit/admit_commit tile rows
  ``XLB_BLOCK_I=n``    pin the completion tile lanes
  ``XLB_FOLD=name``    pin the aggregation strategy (``onehot``/``segment``)

Explicit keyword arguments at a call site outrank the environment; the
environment outranks the cache/sweep; the sweep outranks the static
defaults.  The fold strategy itself is categorical per backend
(``backend.default_fold``) — the sweep only searches tile sizes.
"""

from __future__ import annotations

import math
import os
import time
from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import backend

ENV_AUTOTUNE = "XLB_AUTOTUNE"
ENV_BLOCK_R = "XLB_BLOCK_R"
ENV_BLOCK_I = "XLB_BLOCK_I"
ENV_FOLD = "XLB_FOLD"

DEFAULT_BLOCK_R = 256
DEFAULT_BLOCK_I = 8
BLOCK_R_CANDIDATES = (64, 256, 1024)
BLOCK_I_CANDIDATES = (1, 4, 8, 16)

# (kernel, backend, *shape) → chosen block size
_cache: dict[tuple, int] = {}
_log: list[tuple] = []     # sweep history, for tests/inspection


def clear_cache() -> None:
    _cache.clear()
    _log.clear()


def autotune_enabled() -> bool:
    return os.environ.get(ENV_AUTOTUNE, "1").lower() not in ("0", "false",
                                                             "off")


def _env_int(name: str) -> int | None:
    v = os.environ.get(name, "").strip()
    return int(v) if v else None


def resolve_fold(fold: str | None) -> str:
    """Explicit arg > XLB_FOLD > backend default."""
    if fold is not None:
        return backend.resolve_fold(fold)
    return backend.resolve_fold(os.environ.get(ENV_FOLD, "").strip() or None)


def _time_best(fn, *args, reps: int = 3, trials: int = 3) -> float:
    """Min-of-trials per-call seconds (min, not median: the sweep wants the
    noise floor, and candidates share the same noisy machine)."""
    out = fn(*args)                        # compile outside timing
    jax.block_until_ready(out)
    best = math.inf
    for _ in range(trials):
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / reps)
    return best


def _sweep(key: tuple, candidates, make_fn) -> int:
    """Time each candidate block size, cache and return the fastest.

    Runs under ``jax.core.eval_context()``: plans are usually requested
    while an outer program (the engine's ``serve_step``, a benchmark
    closure) is being traced, and modern JAX stages every op issued during
    tracing — the eval context escapes the ambient trace so the synthetic
    runs compile and execute concretely (``ensure_compile_time_eval``
    is not enough: it inlines the inner jit, which breaks pallas_call)."""
    if key in _cache:
        return _cache[key]
    timings = {}
    with jax.core.eval_context():
        for cand in candidates:
            timings[cand] = _time_best(make_fn(cand))
    best = min(timings, key=timings.get)
    _cache[key] = best
    _log.append((key, best, timings))
    return best


# --------------------------------------------------------------------------- #
# admit / admit_commit
# --------------------------------------------------------------------------- #


def _admit_candidates(R: int) -> list[int]:
    """Tile rows that tile an ``R``-row batch on the tuned backend
    (``route_match.admit_tile_ok``): an illegal tile is never offered, and
    a legal one that fails to compile raises out of the sweep."""
    from repro.kernels.route_match import admit_tile_ok
    interp = backend.default_interpret()
    return sorted({min(b, R) for b in BLOCK_R_CANDIDATES
                   if admit_tile_ok(b, R, interpret=interp)})


def _synthetic_admit(R: int, I: int, C: int, fold: str, commit: bool):
    """A shape-matched workload for the sweep.  The segment fold gates
    per-policy work with runtime ``lax.cond`` on the cluster table, so the
    synthetic config routes traffic to a LEAST_REQUEST cluster *and* keeps
    a WEIGHTED cluster in the table — both heavy branches (water-fill and
    Gumbel argmax) execute, timing the conservative cost curve.  Drains
    are left off (the steady state the serving path runs)."""
    from repro.core.routing_table import (MAX_EPS_PER_CLUSTER, N_FEATURES,
                                          POLICY_LEAST_REQUEST,
                                          POLICY_WEIGHTED, Cluster, Rule,
                                          ServiceConfig, build_state)
    from repro.kernels import route_match as _rm

    eps = [i % max(I, 1) for i in range(min(8, I))]
    state, _ = build_state(
        [ServiceConfig("t", rules=[Rule(0, None, "pool")])],
        [Cluster("pool", endpoints=eps, policy=POLICY_LEAST_REQUEST),
         Cluster("alt", endpoints=eps[:1], policy=POLICY_WEIGHTED)])
    rid = jnp.arange(R, dtype=jnp.int32)
    z = jnp.zeros((R,), jnp.int32)
    feats = jnp.zeros((R, N_FEATURES), jnp.int32)
    gum = jnp.zeros((R, MAX_EPS_PER_CLUSTER), jnp.float32)
    if commit:
        pool = [jnp.full((I, C), -1, jnp.int32), jnp.full((I, C), -1,
                                                          jnp.int32),
                jnp.zeros((I, C), jnp.int32), jnp.zeros((I, C), jnp.int32),
                jnp.zeros((I, C), jnp.int32), jnp.zeros((I, C), jnp.int32)]

        def make_fn(block_r):
            return jax.jit(partial(_rm.admit_commit, block_r=block_r,
                                   fold=fold)), (rid, z, feats, z, z, state,
                                                 *pool, z, gum)
    else:
        free = jnp.ones((I, C), jnp.int32)

        def make_fn(block_r):
            return jax.jit(partial(_rm.admit, block_r=block_r,
                                   fold=fold)), (rid, z, feats, z, state,
                                                 free, z, gum)
    return make_fn


def plan_admit(R: int, pool_shape: tuple, *, block_r: int | None = None,
               fold: str | None = None,
               commit: bool = False) -> tuple[int, str]:
    """Resolve (block_r, fold) for an admit/admit_commit launch of ``R``
    requests over an (I, C) pool.  Shapes only — safe to call mid-trace
    (the sweep runs on synthetic concrete inputs)."""
    fold = resolve_fold(fold)
    if block_r is not None:
        return block_r, fold
    env = _env_int(ENV_BLOCK_R)
    if env is not None:
        return env, fold
    if R <= 0:
        return DEFAULT_BLOCK_R, fold
    I, C = pool_shape
    key = ("admit_commit" if commit else "admit",
           backend.backend_kind(), fold, R, I, C)
    if key in _cache:
        return _cache[key], fold
    cands = _admit_candidates(R)
    if not autotune_enabled() or len(cands) == 1:
        return min(DEFAULT_BLOCK_R, R), fold

    def make_fn(b):     # called under _sweep's compile-time-eval guard
        fn, args = _synthetic_admit(R, I, C, fold, commit)(b)
        return partial(fn, *args)

    return _sweep(key, cands, make_fn), fold


# --------------------------------------------------------------------------- #
# complete
# --------------------------------------------------------------------------- #


def _complete_candidates(I: int) -> list[int]:
    """Tile lanes for an ``I``-lane pool on the tuned backend (multiples of
    8 rows or the whole pool when compiled; the kernel pads the rest)."""
    interp = backend.default_interpret()
    return sorted({min(b, I) for b in BLOCK_I_CANDIDATES + (I,)
                   if backend.tile_ok(b, I, 8, interpret=interp)})


def plan_complete(pool_shape: tuple, *, block_i: int | None = None,
                  fold: str | None = None) -> tuple[int, str]:
    """Resolve (block_i, fold) for a completion launch over an (I, C) pool."""
    from repro.core.routing_table import MAX_ENDPOINTS, MAX_SERVICES
    from repro.kernels import completion as _cp

    fold = resolve_fold(fold)
    if block_i is not None:
        return block_i, fold
    env = _env_int(ENV_BLOCK_I)
    if env is not None:
        return env, fold
    I, C = pool_shape
    key = ("complete", backend.backend_kind(), fold, I, C)
    if key in _cache:
        return _cache[key], fold
    cands = _complete_candidates(I)
    if not autotune_enabled() or len(cands) == 1:
        return min(DEFAULT_BLOCK_I, I), fold

    def make_fn(b):     # called under _sweep's compile-time-eval guard
        pool = [jnp.full((I, C), -1, jnp.int32),
                jnp.full((I, C), -1, jnp.int32),
                jnp.zeros((I, C), jnp.int32), jnp.zeros((I, C), jnp.int32),
                jnp.zeros((I, C), jnp.int32), jnp.ones((I, C), jnp.int32)]
        nxt = jnp.zeros((I, C), jnp.int32)
        load = jnp.zeros((MAX_ENDPOINTS,), jnp.int32)
        rx = jnp.zeros((MAX_SERVICES,), jnp.int32)
        fn = jax.jit(partial(_cp.complete, eos=1, max_len=16, block_i=b,
                             fold=fold))
        return partial(fn, *pool, nxt, load, rx)

    return _sweep(key, cands, make_fn), fold
