"""Connection-completion datapath as one fused Pallas kernel (paper §4.1's
close path: the response-side eBPF program that tears the connection down
without a host round-trip).

``complete`` fuses the whole post-decode completion chain of
``Engine.step`` over the (I, C) connection pool:

  * done detection      — an active slot finishes on EOS or on hitting the
                          length budget (``new_len >= max_len - 1``);
  * load release        — each finished slot decrements its endpoint's
                          outstanding-request counter (``policies.release``);
  * rx traffic metrics  — every active slot adds its per-token response
                          bytes to its service's rx counter;
  * slot free           — finished slots clear req_id/endpoint, zero their
                          length, and drop out of the active set.

Grid: (ceil(I / BI),) sequential over instance-lane tiles; the wrapper
pads the instance axis with empty lanes to whole tiles.  The endpoint-load
decrements and per-service rx bytes accumulate in VMEM scratch across the
grid and are folded into the (E,) / (S,) outputs on the last step — the same
running-counter carry as the admit kernel (``kernels/route_match.py``).
The per-tile aggregation goes through the shared segment-fold seam
(``route_match._seg_sum``, DESIGN.md §5): ``fold="segment"`` scatter-adds
into the scratch counters in O(tile) — the CPU-interpreter default —
while ``fold="onehot"`` folds one pool column at a time through a dense
compare/select reduction, the form Mosaic compiles for the TPU.

The kernel also closes the health-observation loop (DESIGN.md §8): every
step it folds the per-endpoint completion count (the same segment fold as
the load release) and carries two f32 EWMA accumulators exactly like
``ep_load`` — ``ep_inflight_ewma`` (requests in flight at the step, i.e.
ticks-in-flight mass) and ``ep_tput_ewma`` (completions per step).  Their
ratio is the per-endpoint latency estimate under Little's law; the
``HealthPolicy`` daemon (core/health.py) reads it, the kernel never
decides.  The EWMA epilogue is the shared ``health_update`` helper so the
single-shard kernel, the psum-reconciled sharded path, the numpy sidecar
parity, and the ref oracle are bit-exact by construction.

Sequential semantics are pinned by ``kernels.ref.complete_ref`` (bit-exact,
property-tested in tests/test_kernels.py under both folds).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.backend import resolve_fold, resolve_interpret, tile_ok
from repro.kernels.route_match import _seg_sum, _table_spec

RX_BYTES_PER_TOKEN = 2     # response payload attributed per decoded token

# EWMA smoothing for the health accumulators.  In-flight reacts faster than
# throughput so occupancy build-up on a degraded endpoint shows before its
# completion rate has fully decayed.
ALPHA_INFLIGHT = 0.25
ALPHA_TPUT = 0.125


class CompleteResult(NamedTuple):
    """Everything ``Engine.step`` needs from one fused completion launch."""

    req_id: jax.Array     # (I, C) i32, -1 on freed slots
    endpoint: jax.Array   # (I, C) i32, -1 on freed slots
    svc: jax.Array        # (I, C) i32 (unchanged; stale slots keep svc)
    length: jax.Array     # (I, C) i32, 0 on freed slots
    token: jax.Array      # (I, C) i32 last emitted token
    active: jax.Array     # (I, C) i32 0/1
    done: jax.Array       # (I, C) i32 0/1 finished this step
    ep_load: jax.Array    # (E,) i32 counters after release
    rx_bytes: jax.Array   # (S,) i32 per-service rx metric after this step
    done_cnt: jax.Array   # (E,) i32 completions this step (raw fold output)
    inflight_ewma: jax.Array  # (E,) f32 updated in-flight EWMA
    tput_ewma: jax.Array  # (E,) f32 updated completions-per-step EWMA


def health_update(inflight_ewma, tput_ewma, ep_load, done_cnt, *,
                  alpha_inflight: float = ALPHA_INFLIGHT,
                  alpha_tput: float = ALPHA_TPUT):
    """One EWMA step over the integer health observations.

    ``ep_load`` is the occupancy *before* this step's releases (requests in
    flight during the step) and ``done_cnt`` the per-endpoint completions.
    Single source of truth for the f32 epilogue: the fused kernel, the
    sharded psum path, the sidecar baselines and the ref oracle all call
    this on identical integer inputs, so the EWMAs are bit-exact across
    folds and shard counts.
    """
    occ = ep_load.astype(jnp.float32)
    cnt = done_cnt.astype(jnp.float32)
    inflight = inflight_ewma + jnp.float32(alpha_inflight) * (occ - inflight_ewma)
    tput = tput_ewma + jnp.float32(alpha_tput) * (cnt - tput_ewma)
    return inflight.astype(jnp.float32), tput.astype(jnp.float32)


def _complete_kernel(preq_ref, pep_ref, psvc_ref, plen_ref, ptok_ref,
                     pact_ref, nxt_ref, load0_ref, rx0_ref, ewl0_ref,
                     ewt0_ref, oreq_ref, oep_ref, osvc_ref, olen_ref,
                     otok_ref, oact_ref, done_ref, loadout_ref, rxout_ref,
                     cntout_ref, ewlout_ref, ewtout_ref,
                     dec_s, rx_s, *, eos: int, max_len: int, fold: str,
                     alpha_inflight: float, alpha_tput: float):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dec_s[...] = jnp.zeros_like(dec_s)
        rx_s[...] = jnp.zeros_like(rx_s)

    E = load0_ref.shape[0]
    S = rx0_ref.shape[0]

    act = pact_ref[...] > 0
    nxt = nxt_ref[...]
    new_len = jnp.where(act, plen_ref[...] + 1, plen_ref[...])
    done = act & ((nxt == eos) | (new_len >= max_len - 1))

    # ---- slot free ----------------------------------------------------- #
    oreq_ref[...] = jnp.where(done, -1, preq_ref[...])
    oep_ref[...] = jnp.where(done, -1, pep_ref[...])
    osvc_ref[...] = psvc_ref[...]
    olen_ref[...] = jnp.where(done, 0, new_len)
    otok_ref[...] = jnp.where(act, nxt, ptok_ref[...])
    oact_ref[...] = (act & ~done).astype(jnp.int32)
    done_ref[...] = done.astype(jnp.int32)

    # ---- load release (tiled segment fold over endpoints) -------------- #
    pep = pep_ref[...]
    rel = done & (pep >= 0) & (pep < E)
    one = jnp.ones(pep.shape, jnp.int32)
    dec_s[...] = _seg_sum(dec_s[...], jnp.where(rel, jnp.clip(pep, 0, E - 1),
                                                E), one, fold=fold)

    # ---- rx traffic metrics (per active slot, svc >= S drops) ---------- #
    svc = jnp.maximum(psvc_ref[...], 0)
    rx_s[...] = _seg_sum(rx_s[...], jnp.where(act, jnp.minimum(svc, S), S),
                         RX_BYTES_PER_TOKEN * one, fold=fold)

    @pl.when(i == pl.num_programs(0) - 1)
    def _emit():
        loadout_ref[...] = load0_ref[...] - dec_s[...]
        rxout_ref[...] = rx0_ref[...] + rx_s[...]
        cntout_ref[...] = dec_s[...]
        ewl, ewt = health_update(ewl0_ref[...], ewt0_ref[...],
                                 load0_ref[...], dec_s[...],
                                 alpha_inflight=alpha_inflight,
                                 alpha_tput=alpha_tput)
        ewlout_ref[...] = ewl
        ewtout_ref[...] = ewt


def check_block_i(I: int, block_i: int, *, interpret: bool) -> int:
    """The completion tile rows for an ``I``-lane pool: ``block_i`` capped
    at ``I``.  A compiled kernel needs whole (8, 128) tiles, so a tile
    shorter than the pool must be a multiple of 8 rows; anything else is
    refused rather than silently reshaped."""
    block_i = min(block_i, I)
    if block_i < 1:
        raise ValueError(f"block_i must be >= 1, got {block_i}")
    if not tile_ok(block_i, I, 8, interpret=interpret):
        raise ValueError(f"block_i={block_i} does not tile a compiled "
                         f"kernel: use a multiple of 8 or the whole pool "
                         f"({I} lanes)")
    return block_i


def complete(pool_req_id, pool_endpoint, pool_svc, pool_length, pool_token,
             pool_active, nxt, ep_load, rx_bytes, ep_inflight_ewma=None,
             ep_tput_ewma=None, *, eos: int, max_len: int,
             block_i: int = 8, fold: str | None = None,
             alpha_inflight: float = ALPHA_INFLIGHT,
             alpha_tput: float = ALPHA_TPUT,
             interpret: bool | None = None) -> CompleteResult:
    """Fused completion over the pool after one decode step.

    pool_*: (I, C) connection state (active may be bool or i32); nxt: (I, C)
    i32 tokens emitted this step; ep_load: (E,) i32; rx_bytes: (S,) i32;
    ep_inflight_ewma / ep_tput_ewma: (E,) f32 carried health accumulators
    (None → zeros, i.e. a cold start).
    ``eos`` / ``max_len`` are compile-time constants (engine attributes).
    """
    I, C = pool_req_id.shape
    E = ep_load.shape[0]
    S = rx_bytes.shape[0]
    if ep_inflight_ewma is None:
        ep_inflight_ewma = jnp.zeros((E,), jnp.float32)
    if ep_tput_ewma is None:
        ep_tput_ewma = jnp.zeros((E,), jnp.float32)
    interpret = resolve_interpret(interpret)
    block_i = check_block_i(I, block_i, interpret=interpret)
    # pad the instance axis to whole tiles with empty lanes (inactive: no
    # completion, release or rx), sliced off the outputs below
    Ip = -(-I // block_i) * block_i
    lanes = [pool_req_id, pool_endpoint, pool_svc, pool_length, pool_token,
             pool_active, nxt]
    fill = [-1, -1, 0, 0, 0, 0, 0]
    lanes = [jnp.pad(a.astype(jnp.int32), ((0, Ip - I), (0, 0)),
                     constant_values=f) for a, f in zip(lanes, fill)]
    grid = (Ip // block_i,)
    lane = pl.BlockSpec((block_i, C), lambda i: (i, 0))
    o = pl.pallas_call(
        functools.partial(_complete_kernel, eos=eos, max_len=max_len,
                          fold=resolve_fold(fold),
                          alpha_inflight=alpha_inflight,
                          alpha_tput=alpha_tput),
        grid=grid,
        in_specs=[lane] * 7 + [_table_spec((E,)), _table_spec((S,)),
                               _table_spec((E,)), _table_spec((E,))],
        out_specs=[lane] * 7 + [_table_spec((E,)), _table_spec((S,)),
                                _table_spec((E,)), _table_spec((E,)),
                                _table_spec((E,))],
        out_shape=[jax.ShapeDtypeStruct((Ip, C), jnp.int32)] * 7
                  + [jax.ShapeDtypeStruct((E,), jnp.int32),
                     jax.ShapeDtypeStruct((S,), jnp.int32),
                     jax.ShapeDtypeStruct((E,), jnp.int32),
                     jax.ShapeDtypeStruct((E,), jnp.float32),
                     jax.ShapeDtypeStruct((E,), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((E,), jnp.int32),
                        pltpu.VMEM((S,), jnp.int32)],
        interpret=interpret,
        name="xlb_complete",
    )(*lanes, ep_load, rx_bytes, ep_inflight_ewma.astype(jnp.float32),
      ep_tput_ewma.astype(jnp.float32))
    o = [x[:I] for x in o[:7]] + list(o[7:])
    return CompleteResult(*o)
