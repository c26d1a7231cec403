"""Sharded-admission measurement for ``benchmarks/run.py::bench_shard``.

Measures the mesh-sharded admission datapath (``ops.admit_commit_sharded``:
per-shard fused kernel + psum reconciliation + commit relay, DESIGN.md §7)
against the single-shard fused kernel on the same batch.  ``measure`` runs
on whatever devices its process sees: ``run._sharded_leg`` calls it
in-process, or by name in a child on an M-device virtual host mesh for the
CPU rehearsal.  On the CPU interpreter the collectives pay host-loop
overhead and the M "hosts" timeshare one machine, so the ratio there is an
advisory trend row — the real read is the TPU leg, where the shards are
distinct chips and the reconciliation is one ICI pass.
"""

from __future__ import annotations


def measure(shards: int) -> dict:
    """Single-shard vs ``shards``-way sharded admission timings."""
    import jax
    import jax.numpy as jnp

    from benchmarks import common
    from benchmarks.run import _time_us
    from repro.core.balancer import PoolState, RequestBatch
    from repro.core.routing_table import MAX_EPS_PER_CLUSTER
    from repro.kernels import ops
    from repro.launch.mesh import make_shard_mesh

    n_instances, slots = 8, 64
    st = common.build_routing(n_instances)
    mesh = make_shard_mesh(shards)
    record = {"shards": shards, "batch": [], "single_us": [],
              "sharded_us": [], "ratio": []}
    for R in (256, 1024):
        ks = jax.random.split(jax.random.PRNGKey(0), 2)
        reqs = RequestBatch(
            req_id=jnp.arange(R, dtype=jnp.int32),
            svc=jnp.zeros((R,), jnp.int32),
            features=jnp.zeros((R, 8), jnp.int32),
            token=jnp.zeros((R,), jnp.int32),
            msg_bytes=jnp.full((R,), 128, jnp.int32))
        rnd = jax.random.randint(ks[0], (R,), 0, 1 << 30, dtype=jnp.int32)
        gum = jax.random.gumbel(ks[1], (R, MAX_EPS_PER_CLUSTER),
                                jnp.float32)
        pool = PoolState.init(n_instances, slots)

        def single():
            return ops.admit_commit(reqs, st, pool, rnd, gum)

        def sharded():
            return ops.admit_commit_sharded(reqs, st, pool, rnd, gum,
                                            mesh=mesh)

        t1 = _time_us(single, reps=max(5, 1024 // R))
        t2 = _time_us(sharded, reps=max(5, 1024 // R))
        record["batch"].append(R)
        record["single_us"].append(round(t1, 2))
        record["sharded_us"].append(round(t2, 2))
        record["ratio"].append(round(t1 / t2, 3))
    return record
