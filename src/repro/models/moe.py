"""Mixture-of-Experts FFN driven by the XLB relay (core.relay).

Token→expert routing *is* L7 load balancing: content-based destination
selection (router logits = the route match), a balancing policy (gate-greedy
top-k, optionally least-request bias — the paper's LB algorithms), capacity =
the i-sock connection-pool size, and the relay hop = the socket relay
(all-to-all over the expert-parallel mesh axis).

Supports the assigned MoE shapes:
  * deepseek-v2: 2 shared experts + 160 routed top-6, first layer dense
  * arctic: 128 routed top-2 with a parallel dense residual MLP
  * jamba: 16 routed top-2 on alternate layers
"""

from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core import relay
from repro.models.layers import Params, dense_init, ffn, init_ffn, split_keys


class MoEMetrics(NamedTuple):
    aux_loss: jax.Array        # load-balancing loss (Switch-style)
    z_loss: jax.Array          # router logit z-loss
    overflow_frac: jax.Array   # dropped-token fraction (pool exhaustion)
    load: jax.Array            # (E,) tokens routed per expert (pre-drop)

    @staticmethod
    def zero(n_experts: int) -> "MoEMetrics":
        z = jnp.zeros(())
        return MoEMetrics(z, z, z, jnp.zeros((n_experts,), jnp.int32))


def init_moe(key, cfg: ModelConfig, dtype) -> Params:
    m = cfg.moe
    D, E, Fe = cfg.d_model, m.n_experts, m.d_ff_expert
    ks = split_keys(key, 6)
    p: Params = {
        "router": dense_init(ks[0], (D, E), jnp.float32),
        "w_in": dense_init(ks[1], (E, D, Fe), dtype),
        "w_gate": dense_init(ks[2], (E, D, Fe), dtype),
        "w_out": dense_init(ks[3], (E, Fe, D), dtype),
    }
    if m.n_shared_experts:
        p["shared"] = init_ffn(ks[4], D, m.n_shared_experts * Fe, cfg.ffn_act, dtype)
    if m.dense_residual:
        p["residual"] = init_ffn(ks[5], D, cfg.d_ff, cfg.ffn_act, dtype)
    return p


def capacity_for(n_tokens: int, cfg: ModelConfig) -> int:
    """Connection-pool size per expert given ``n_tokens`` routed tokens."""
    m = cfg.moe
    c = math.ceil(n_tokens * m.top_k * m.capacity_factor / m.n_experts)
    return max(8, -(-c // 8) * 8)          # round up to a multiple of 8


def _expert_ffn(w, pool):
    """pool: (E, C, D) → (E, C, D); swiglu per expert."""
    h = jnp.einsum("ecd,edf->ecf", pool, w["w_in"])
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", pool, w["w_gate"]))
    return jnp.einsum("ecf,efd->ecd", h * g, w["w_out"])


def route(cfg: ModelConfig, p: Params, xf: jax.Array,
          router_bias: Optional[jax.Array] = None):
    """Router: logits → (top-k weights (T,k), expert ids (T,k), aux, z).

    ``router_bias``: optional (E,) least-request bias (aux-loss-free balancing
    — the XLB least-request policy applied to experts).  Bias shifts
    *selection* only; combine weights use unbiased gates.
    """
    m = cfg.moe
    logits = xf.astype(jnp.float32) @ p["router"].astype(jnp.float32)  # (T,E)
    gates = jax.nn.softmax(logits, axis=-1)
    sel = gates if router_bias is None else gates + router_bias[None, :]
    _, idx = jax.lax.top_k(sel, m.top_k)                       # (T,k)
    weights = jnp.take_along_axis(gates, idx, axis=-1)         # (T,k)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9)
    # Switch aux loss: E * sum_e f_e * P_e
    me = jnp.mean(gates, axis=0)                               # (E,)
    ce = jnp.mean(
        jax.nn.one_hot(idx, m.n_experts, dtype=jnp.float32).sum(1), axis=0)
    aux = m.n_experts * jnp.sum(me * ce)
    z = jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) ** 2)
    return weights, idx.astype(jnp.int32), aux, z


def moe_ffn(cfg: ModelConfig, p: Params, x: jax.Array, *,
            method: str = "sort",
            ep: Optional[tuple] = None,
            router_bias: Optional[jax.Array] = None,
            explicit_fsdp: bool = False,
            ) -> tuple[jax.Array, MoEMetrics]:
    """MoE FFN. x: (B, S, D).

    ``ep=(mesh, tok_axes)`` enables the expert-parallel a2a relay via
    shard_map; ``tok_axes`` is the tuple of mesh axes the flattened token
    stream is sharded over (must include "model", the expert-owner axis).

    ``explicit_fsdp``: gather the dp-sharded expert weights *inside* the
    shard_map with an explicit bf16 ``all_gather`` (transpose = bf16
    reduce-scatter for the weight grads) instead of letting GSPMD insert the
    gather outside — on the CPU backend GSPMD converts to f32 first (2×
    wire bytes), and on any backend this pins gather-per-layer-per-pass.
    """
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    weights, idx, aux, z = route(cfg, p, xf, router_bias)
    k = m.top_k
    N = T * k
    x_rep = jnp.repeat(xf, k, axis=0)                          # (N,D) t-major
    idx_flat = idx.reshape(N)
    w_flat = weights.reshape(N)

    if ep is not None:
        mesh, tok_axes = ep
        dp_axes = tuple(a for a in tok_axes if a != "model")
        n_shards = math.prod(mesh.shape[a] for a in tok_axes)
        cap = capacity_for(T // n_shards, cfg)
        use_exp = explicit_fsdp and bool(dp_axes)

        def body(xx, ii, ww, pp):
            if use_exp:
                # explicit ZeRO-3 gather, bf16 on the wire (fwd AG, bwd RS)
                pp = {
                    "w_in": jax.lax.all_gather(pp["w_in"], dp_axes, axis=1,
                                               tiled=True),
                    "w_gate": jax.lax.all_gather(pp["w_gate"], dp_axes,
                                                 axis=1, tiled=True),
                    "w_out": jax.lax.all_gather(pp["w_out"], dp_axes, axis=2,
                                                tiled=True),
                }
            out, meta = relay.sharded_apply(
                xx, ii, ww, n_dest=m.n_experts, capacity=cap, axis="model",
                backend_fn=_expert_ffn, backend_params=pp)
            # sharded_apply already reduces meta over its relay axis
            # ("model"): load is global pre-drop, overflow_frac the axis
            # mean — only the data axes remain to fold in here
            ovf = (jax.lax.pmean(meta.overflow_frac, dp_axes) if dp_axes
                   else meta.overflow_frac)
            load = (jax.lax.psum(meta.load, dp_axes) if dp_axes
                    else meta.load)
            return out, ovf, load

        wdict = {n: p[n] for n in ("w_in", "w_gate", "w_out")}
        if use_exp:
            wspecs = {"w_in": P("model", dp_axes, None),
                      "w_gate": P("model", dp_axes, None),
                      "w_out": P("model", None, dp_axes)}
        else:
            wspecs = {n: P("model", None, None) for n in wdict}
        out_flat, overflow, load = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(tok_axes, None), P(tok_axes), P(tok_axes), wspecs),
            out_specs=(P(tok_axes, None), P(), P()),
            check_vma=False,
        )(x_rep, idx_flat, w_flat, wdict)
    else:
        cap = capacity_for(T, cfg)
        if method == "einsum":
            buf, meta, d_oh = relay.relay_dispatch_einsum(x_rep, idx_flat,
                                                          m.n_experts, cap)
            out_buf = _expert_ffn(p, buf)
            out_flat = relay.relay_combine_einsum(out_buf, d_oh, w_flat)
        else:
            buf, meta = relay.relay_dispatch(x_rep, idx_flat, m.n_experts, cap,
                                             method=method)
            out_buf = _expert_ffn(p, buf)
            out_flat = relay.relay_combine(out_buf, meta, w_flat)
        overflow, load = meta.overflow_frac, meta.load

    out = out_flat.reshape(T, k, D).sum(axis=1).reshape(B, S, D)

    if "shared" in p:
        out = out + ffn(p["shared"], x, cfg.ffn_act)
    if "residual" in p:
        out = out + ffn(p["residual"], x, cfg.ffn_act)
    return out, MoEMetrics(aux, z, overflow, load)
