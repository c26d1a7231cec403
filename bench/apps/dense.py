"""Plain reference of a dense decoder: embedding, RMSNorm, rotary GQA
attention, SwiGLU or GELU FFN, head (``xlb-service-model`` and every
configuration that names no ``reference``).  It imports nothing of the
program."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# Application sizes the configuration states, checked against the
# program's registered model so that the reference and the program run
# the same architecture.
KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
        "d_ff", "vocab", "ffn_act", "norm_eps", "rope_theta")


def check(app: dict, cfg) -> None:
    """The configuration's application entry must be the program's model."""
    bad = [k for k in KEYS if app[k] != getattr(cfg, k)]
    if bad or cfg.family != "dense" or cfg.moe.n_experts or cfg.is_encdec:
        raise ValueError(f"application {app['name']!r} differs from the "
                         f"program's model in {bad or ['family']}")


def _rms(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
    y = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x: (B, S, H, hd); rotate-half rotary embedding at positions 0..S-1."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def forward_logits(app: dict, params: dict, tokens, dtype=jnp.float32):
    """Logits (B, S, V) of a causal forward over ``tokens`` (B, S).  The
    application is described by the configuration's ``application``
    entry.  ``dtype`` is the precision it is computed in: float32 at the
    highest matmul precision; bfloat16; or float8_e4m3fn, every weight and
    every matmul operand rounded to fp8, products accumulated in float32
    and activations carried in bfloat16."""
    H, K, hd = app["n_heads"], app["n_kv_heads"], app["head_dim"]
    eps, theta = app["norm_eps"], app["rope_theta"]
    act = jnp.bfloat16 if dtype == jnp.float8_e4m3fn else dtype

    def rnd(a):
        return a.astype(dtype).astype(act)

    def mm(eq, a, b):
        return jnp.einsum(eq, rnd(a), rnd(b),
                          preferred_element_type=jnp.float32)

    p = jax.tree.map(rnd, params)
    B, S = tokens.shape
    G = H // K
    mask = jnp.tril(jnp.ones((S, S), bool))
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        x = p["embed"][tokens]
        for li in range(app["n_layers"]):
            lp = jax.tree.map(lambda a: a[li], p["blocks"])
            at, f = lp["attn"], lp["ffn"]
            h = _rms(x, lp["norm1"], eps)
            q = mm("bsd,de->bse", h, at["wq"]).astype(act)
            k = mm("bsd,de->bse", h, at["wk"]).astype(act)
            v = mm("bsd,de->bse", h, at["wv"]).astype(act)
            q = _rope(q.reshape(B, S, H, hd), theta).reshape(B, S, K, G, hd)
            k = _rope(k.reshape(B, S, K, hd), theta)
            s = mm("bqkgh,bskh->bkgqs", q, k)
            s = jnp.where(mask, s / np.sqrt(hd).astype(np.float32), -1e30)
            w = jax.nn.softmax(s, axis=-1)
            o = mm("bkgqs,bskh->bqkgh", w, v.reshape(B, S, K, hd))
            o = o.reshape(B, S, H * hd).astype(act)
            x = x + mm("bsd,de->bse", o, at["wo"]).astype(act)
            h = _rms(x, lp["norm2"], eps)
            u = mm("bsd,df->bsf", h, f["w_in"])
            if app["ffn_act"] == "swiglu":
                u = jax.nn.silu(mm("bsd,df->bsf", h, f["w_gate"])) * u
            else:
                u = jax.nn.gelu(u)
            x = x + mm("bsf,fd->bsd", u.astype(act), f["w_out"]).astype(act)
        x = _rms(x, p["norm_f"], eps)
        return mm("bsd,dv->bsv", x, p["head"])


def decode_flops(app: dict, batch: int, cache_len: int) -> float:
    """FLOPs of one decode step over ``batch`` slots."""
    D, H, K, hd = app["d_model"], app["n_heads"], app["n_kv_heads"], \
        app["head_dim"]
    Fd, L = app["d_ff"], app["n_layers"]
    vocab = -(-app["vocab"] // 256) * 256
    ffn = (3 if app["ffn_act"] == "swiglu" else 2) * D * Fd
    per_layer = D * H * hd + 2 * D * K * hd + H * hd * D + ffn
    matmul = 2 * batch * (L * per_layer + D * vocab)
    attn = 4 * batch * L * H * hd * cache_len
    return float(matmul + attn)
