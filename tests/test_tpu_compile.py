"""Ahead-of-time compiles of the serving datapath kernels for a TPU v5e.

The CPU suite runs every kernel through the Pallas interpreter, which
accepts constructs Mosaic refuses (vector gathers out of refs, cumsum,
scatter, 2-D → 1-D reshapes, tiles off the (8, 128) grid).  These tests
compile the kernels with ``interpret=False`` for a described — not
attached — ``v5e:2x2`` topology at the serving shapes: a 256-request
admission batch, a 50-instance × 8-slot pool, the full 512-endpoint
routing tables, and the 4-way sharded path on a 52-instance pool.  Nothing
runs; a compile that passes is not a chip run.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core.routing_table import (MAX_EPS_PER_CLUSTER, N_FEATURES,
                                      empty_state)

R, I, C = 256, 50, 8           # one admission batch over the 50-lane fleet
I_SHARDED, SHARDS = 52, 4      # smallest multiple of 4 at or above 50


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_cache():
    """Compiles for a described chip are written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _state(sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding),
                        empty_state())


def _batch(n, sharding):
    i32 = lambda *s: _spec(s, jnp.int32, sharding)
    return (i32(n), i32(n), i32(n, N_FEATURES), i32(n), i32(n))


def _pool(n_inst, sharding):
    return tuple(_spec((n_inst, C), jnp.int32, sharding) for _ in range(6))


def _custom_calls(compiled) -> int:
    return compiled.as_text().count("tpu_custom_call")


@pytest.mark.parametrize("rows,block_r", [(R, R), (4 * R, R)])
def test_admit_commit_compiles_for_v5e(one_chip, no_cache, rows, block_r):
    """One tile (the serving batch) and a 4-tile grid, whose per-request
    operands must be cut below their compiled 1-D layout."""
    from repro.kernels import route_match as _rm

    def f(rid, svc, feats, mb, tok, state, *rest):
        *pool, rnd, gum = rest
        return _rm.admit_commit(rid, svc, feats, mb, tok, state, *pool, rnd,
                                gum, block_r=block_r, fold="onehot",
                                interpret=False)

    args = (*_batch(rows, one_chip), _state(one_chip), *_pool(I, one_chip),
            _spec((rows,), jnp.int32, one_chip),
            _spec((rows, MAX_EPS_PER_CLUSTER), jnp.float32, one_chip))
    compiled = jax.jit(f).lower(*args).compile()
    assert _custom_calls(compiled) >= 1


def test_route_match_compiles_for_v5e(one_chip, no_cache):
    """The stateless match + least-request building block."""
    from repro.kernels import route_match as _rm

    def f(svc, feats, state):
        return _rm.route_match(svc, feats, state, block_r=R, fold="onehot",
                               interpret=False)

    args = (_spec((R,), jnp.int32, one_chip),
            _spec((R, N_FEATURES), jnp.int32, one_chip), _state(one_chip))
    compiled = jax.jit(f).lower(*args).compile()
    assert _custom_calls(compiled) >= 1


def test_complete_compiles_for_v5e(one_chip, no_cache):
    from repro.kernels import completion as _cp
    E = empty_state().ep_load.shape[0]
    S = empty_state().svc_rule_start.shape[0]

    def f(*a):
        return _cp.complete(*a, eos=1, max_len=24, block_i=8,
                            fold="onehot", interpret=False)

    args = (*_pool(I, one_chip), _spec((I, C), jnp.int32, one_chip),
            _spec((E,), jnp.int32, one_chip), _spec((S,), jnp.int32, one_chip),
            _spec((E,), jnp.float32, one_chip),
            _spec((E,), jnp.float32, one_chip))
    compiled = jax.jit(f).lower(*args).compile()
    assert _custom_calls(compiled) >= 1


def test_admit_refuses_an_untiled_or_oversized_block():
    """Compiled admission tiles are 128-lane multiples of at most
    MAX_TILE_ROWS rows (1024 rows exhaust the scoped VMEM), or the whole
    batch when it is that small."""
    from repro.kernels import route_match as _rm
    assert _rm.admit_tile_ok(256, 1024, interpret=False)
    assert _rm.admit_tile_ok(200, 200, interpret=False)
    assert not _rm.admit_tile_ok(64, 256, interpret=False)
    assert not _rm.admit_tile_ok(1024, 1024, interpret=False)
    assert _rm.admit_tile_ok(64, 256, interpret=True)


def test_complete_refuses_an_untiled_block():
    """A tile shorter than the pool that is not a multiple of 8 rows is
    refused up front (the interpreter would accept it)."""
    from repro.kernels import completion as _cp
    with pytest.raises(ValueError, match="multiple of 8"):
        _cp.check_block_i(I, 2, interpret=False)
    assert _cp.check_block_i(I, 2, interpret=True) == 2
    assert _cp.check_block_i(I, 64, interpret=False) == I


def test_sharded_admit_and_complete_compile_for_v5e_2x2(topo, no_cache):
    from repro.kernels import shard_admit as _sa
    mesh = Mesh(np.asarray(topo.devices[:SHARDS]), ("shard",))
    sh = NamedSharding(mesh, P("shard"))
    rep = NamedSharding(mesh, P())
    E = empty_state().ep_load.shape[0]
    S = empty_state().svc_rule_start.shape[0]

    def admit(rid, svc, feats, mb, tok, state, *rest):
        *pool, rnd, gum = rest
        return _sa.admit_commit_sharded(
            rid, svc, feats, mb, tok, state, *pool, rnd, gum, mesh=mesh,
            block_r=R, fold="onehot", interpret=False)

    args = (*_batch(R, sh), _state(rep), *_pool(I_SHARDED, sh),
            _spec((R,), jnp.int32, sh),
            _spec((R, MAX_EPS_PER_CLUSTER), jnp.float32, sh))
    compiled = jax.jit(admit).lower(*args).compile()
    assert _custom_calls(compiled) >= 1

    def complete(*a):
        return _sa.complete_sharded(*a, mesh=mesh, eos=1, max_len=24,
                                    block_i=8, fold="onehot",
                                    interpret=False)

    args = (*_pool(I_SHARDED, sh), _spec((I_SHARDED, C), jnp.int32, sh),
            _spec((E,), jnp.int32, rep), _spec((S,), jnp.int32, rep),
            _spec((E,), jnp.float32, rep), _spec((E,), jnp.float32, rep))
    compiled = jax.jit(complete).lower(*args).compile()
    assert _custom_calls(compiled) >= 1
