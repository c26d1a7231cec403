"""A deployment built from its configuration file: the application's
weights, the routing through ``ControlPlane``, the engine and the
``ServeLoop`` that the measured window drives.

The configuration file (``bench/configs/<name>.json``) is data: engine,
fleet, the engine's kernel tiles, services with their rules, clusters
with their policies, and the application with its published sizes and
the dtype it is served in.  Tiles stated in the file are passed to the
engine, so that no run sweeps for them and every run serves the same
compiled program.
"""

from __future__ import annotations

import dataclasses
import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

# Application sizes the configuration states, checked against the
# program's registered model so that the reference and the program run
# the same architecture.
APP_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "ffn_act", "norm_eps", "rope_theta")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def check_application(app: dict, cfg) -> None:
    """The configuration's application entry must be the program's model."""
    bad = [k for k in APP_KEYS if app[k] != getattr(cfg, k)]
    if bad or cfg.family != "dense" or cfg.moe.n_experts or cfg.is_encdec:
        raise ValueError(f"application {app['name']!r} differs from the "
                         f"program's model in {bad or ['family']}")


def param_shapes(cfg, dtype):
    """The parameter tree the program's decode expects (shapes only)."""
    from repro.models import model as M
    return jax.eval_shape(lambda k: M.init_params(cfg, k, dtype=dtype),
                          jax.random.PRNGKey(0))


def make_params(shapes, seed: int):
    """The application's weights from ``seed``, made on the device in one
    jitted call in the dtype they are served in: matrices normal over
    sqrt(fan-in), embeddings unit normal, norm scales 1 + 0.1 normal."""
    leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

    def fill(key):
        out = []
        for n, (path, s) in enumerate(leaves):
            name = path[-1].key
            z = jax.random.normal(jax.random.fold_in(key, n), s.shape,
                                  jnp.float32)
            if name.startswith("norm"):
                v = 1.0 + 0.1 * z
            elif name == "embed":
                v = z
            else:
                v = z / np.sqrt(s.shape[-2])
            out.append(v.astype(s.dtype))
        return jax.tree_util.tree_unflatten(tree, out)

    return jax.jit(fill)(jax.random.PRNGKey(seed))


@dataclasses.dataclass
class Deployment:
    config: dict
    max_len: int
    cfg: object              # the program's model config
    params: object
    loop: object             # ServeLoop
    service_ids: dict        # service name → id the loop routes on
    tables: reference.RefTables

    @property
    def n_slots(self) -> int:
        return self.config["instances"] * self.config["slots"]


def build(config: dict, max_len: int, seed: int) -> Deployment:
    """Boot the configuration's fleet with weights from ``seed``."""
    from repro.configs import get_config
    from repro.core.balancer import make_balancer
    from repro.core.control import ControlPlane
    from repro.core.routing_table import (POLICY_NAMES, Cluster, Rule,
                                          ServiceConfig)
    from repro.runtime.serve_loop import ServeLoop

    app = config["application"]
    cfg = get_config(app["name"])
    check_application(app, cfg)
    dtype = jnp.dtype(app["dtype"])
    params = make_params(param_shapes(cfg, dtype), seed)
    fields = reference.HEADER_FIELDS
    services = [ServiceConfig(s["name"], rules=[
        Rule(fields.index(r["header"]), r["value"], r["cluster"])
        for r in s["rules"]]) for s in config["services"]]
    clusters = []
    for c in config["clusters"]:
        lanes, weights = reference.cluster_endpoints(c)
        clusters.append(Cluster(c["name"], endpoints=lanes,
                                policy=POLICY_NAMES[c["policy"]],
                                weights=weights))
    cp = ControlPlane(services, clusters)
    eng = make_balancer(config["engine"], cfg, config["instances"],
                        config["slots"], max_len,
                        **config.get("kernel_tiles", {}))
    loop = ServeLoop(eng, params, cp, admit_batch=config["admit_batch"],
                     dtype=dtype)
    tables = reference.RefTables.build(
        config, int(loop.state.routing.maglev_table.shape[1]))
    return Deployment(config, max_len, cfg, params, loop,
                      {s["name"]: i for i, s in enumerate(config["services"])},
                      tables)
