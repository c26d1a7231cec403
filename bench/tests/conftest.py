"""Fixtures shared by the harness's tests."""

import jax
import jax.numpy as jnp
import pytest


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
