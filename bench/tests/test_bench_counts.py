"""Counted operations and bytes: taken from shapes, so the same whatever
fold implements a kernel; and the decode's FLOPs by hand."""

import jax
import jax.numpy as jnp
import pytest

from bench import counts
from bench.tests import tiny


@pytest.fixture(scope="module")
def fleet_state():
    """An engine state of the fleet50 shapes (abstract: nothing runs)."""
    from repro.configs import get_config
    from repro.core.balancer import make_balancer
    from repro.core.control import ControlPlane
    from repro.core.routing_table import Cluster, Rule, ServiceConfig
    cp = ControlPlane([ServiceConfig("api", [Rule(0, "/api", "fleet")])],
                      [Cluster("fleet", list(range(50)))])
    eng = make_balancer("xlb", get_config("xlb-service-model"), 50, 8, 16)
    return jax.eval_shape(lambda: eng.init_state(cp.snapshot(),
                                                 dtype=jnp.float32))


def test_costs_do_not_depend_on_the_fold(fleet_state):
    onehot = counts.kernel_shapes(fleet_state, 256, fold="onehot")
    segment = counts.kernel_shapes(fleet_state, 256, fold="segment")
    assert onehot == segment
    ops, moved = onehot["xlb_admit_commit"]
    assert ops == 256 * (8 + 64 + 8)
    # the Maglev table alone is 64 x 521 int32
    assert moved > 64 * 521 * 4
    ops, moved = onehot["xlb_complete"]
    assert ops == 50 * 8 * counts.COMPLETE_SLOT_OPS + 512 * counts.EWMA_OPS


def test_decode_flops_by_hand():
    app = tiny.APP
    # per layer: q 128x128, k and v 128x64 each, o 128x128, swiglu 3x128x256
    per_layer = 128 * 128 + 2 * 128 * 64 + 128 * 128 + 3 * 128 * 256
    matmul = 2 * 400 * (2 * per_layer + 128 * 512)
    attn = 4 * 400 * 2 * 4 * 32 * 16
    assert counts.decode_flops(app, 400, 16) == matmul + attn
