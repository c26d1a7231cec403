"""A stand-in reference for DeepSeek-V2's smoke configuration (latent
attention, shared and routed experts), for the test that a non-dense
application is served through the harness from new files only.

Unlike a reference of ``bench/apps``, it borrows the program's own
full-sequence forward: the test shows that an application, its reference
and a kernel's cost arrive as files the registry finds, not that the
program's decode of this model is right.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff",
        "vocab")


def _cfg(app: dict):
    from repro.configs import get_config
    return get_config(app["name"])


def check(app: dict, cfg) -> None:
    bad = [k for k in KEYS if app[k] != getattr(cfg, k)]
    if bad or cfg.family != "moe" or cfg.mla is None:
        raise ValueError(f"application {app['name']!r} differs from the "
                         f"program's model in {bad or ['family']}")


def forward_logits(app: dict, params, tokens, dtype=jnp.float32):
    from repro.models import model as M
    p = jax.tree.map(lambda a: a.astype(dtype), params)
    prec = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(prec):
        logits, _ = M.forward(_cfg(app), p, tokens)
    return logits


def decode_flops(app: dict, batch: int, cache_len: int) -> float:
    """The head's FLOPs alone: a stand-in count."""
    return float(2 * batch * app["d_model"] * app["vocab"])
