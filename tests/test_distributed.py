"""Distributed-execution tests: run a subprocess with 4 virtual host devices
(XLA_FLAGS must be set before jax init, hence the subprocess) and verify the
expert-parallel a2a relay + sharded train step EXECUTE correctly — the
dry-run only proves they compile."""

import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import get_config, smoke_config
from repro.models import moe as moe_mod
from repro.models import model as M
from repro.models.transformer import RunCtx
from repro.sharding.specs import MeshSpec

from repro.launch.mesh import make_host_mesh
mesh = make_host_mesh(2, 2)
ms = MeshSpec(mesh)

# --- 1) EP relay (shard_map + all_to_all) == local scatter dispatch ------- #
cfg = smoke_config(get_config("deepseek-v2-236b"))
key = jax.random.PRNGKey(0)
p = moe_mod.init_moe(key, cfg, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model))

local_out, local_m = moe_mod.moe_ffn(cfg, p, x, method="sort")

with mesh:
    ep_fn = jax.jit(lambda p, x: moe_mod.moe_ffn(
        cfg, p, x, ep=(mesh, ("data", "model"))))
    ep_out, ep_m = ep_fn(p, x)
np.testing.assert_allclose(np.asarray(local_out), np.asarray(ep_out),
                           rtol=2e-4, atol=2e-4)
assert int(ep_m.load.sum()) == int(local_m.load.sum())
print("EP relay matches local dispatch")

# --- 2) sharded train step executes and matches single-device loss ------- #
ctx = RunCtx(shard=ms.constrain, tp_size=2)
params = M.init_params(cfg, jax.random.PRNGKey(2), dtype=jnp.float32)
tok = jax.random.randint(jax.random.PRNGKey(3), (4, 32), 0, cfg.vocab)
batch = {"tokens": tok, "labels": jnp.roll(tok, -1, 1)}

loss_ref, _ = M.loss_fn(cfg, params, batch)
with mesh:
    p_sh = ms.params_shardings(params)
    params_d = jax.device_put(params, p_sh)
    batch_d = jax.device_put(batch, ms.batch_shardings(batch))
    loss_sh, _ = jax.jit(lambda p, b: M.loss_fn(cfg, p, b, ctx=ctx))(
        params_d, batch_d)
np.testing.assert_allclose(float(loss_ref), float(loss_sh), rtol=2e-4)
print("sharded loss matches single-device loss")

# --- 3) GQA expansion under real sharding (chameleon family) -------------- #
cfg2 = smoke_config(get_config("chameleon-34b"))
params2 = M.init_params(cfg2, jax.random.PRNGKey(4), dtype=jnp.float32)
tok2 = jax.random.randint(jax.random.PRNGKey(5), (4, 32), 0, cfg2.vocab)
ctx2 = RunCtx(shard=ms.constrain, tp_size=2, q_chunk=16)
logits_ref, _ = M.forward(cfg2, params2, tok2)
with mesh:
    logits_sh, _ = jax.jit(lambda p, t: M.forward(cfg2, p, t, ctx=ctx2))(
        jax.device_put(params2, ms.params_shardings(params2)), tok2)
np.testing.assert_allclose(np.asarray(logits_ref), np.asarray(logits_sh),
                           rtol=5e-4, atol=5e-4)
print("sharded+chunked forward matches unsharded")
"""


@pytest.mark.timeout(900)
def test_distributed_execution_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=850,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    assert "EP relay matches local dispatch" in out.stdout
    assert "sharded loss matches single-device loss" in out.stdout
    assert "sharded+chunked forward matches unsharded" in out.stdout
