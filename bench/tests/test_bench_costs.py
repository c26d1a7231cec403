"""Counted operations and bytes, one file per kernel: taken from shapes, so
the same whatever fold implements a kernel, found by the kernel's name,
and counted only for a deployment that runs the kernel."""

import shutil
from pathlib import Path

import pytest

from bench import costs
from bench.costs import xlb_admit_commit, xlb_complete
from bench.tests import tiny

STAND_IN = Path(__file__).resolve().parent / "stand_in"


def test_costs_do_not_depend_on_the_fold(fleet_state):
    for mod in (xlb_admit_commit, xlb_complete):
        assert mod.cost(fleet_state, {}, tiny.APP, 256, fold="onehot") == \
            mod.cost(fleet_state, {}, tiny.APP, 256, fold="segment")
    ops, moved = xlb_admit_commit.cost(fleet_state, {}, tiny.APP, 256,
                                       fold="onehot")
    assert ops == 256 * (8 + 64 + 8)
    # the Maglev table alone is 64 x 521 int32
    assert moved > 64 * 521 * 4
    ops, moved = xlb_complete.cost(fleet_state, {}, tiny.APP, 256,
                                   fold="onehot")
    assert ops == 50 * 8 * xlb_complete.COMPLETE_SLOT_OPS \
        + 512 * xlb_complete.EWMA_OPS


def test_every_cost_file_is_counted_by_its_name(fleet_state):
    """The kernels are the cost files' names, one launch of each counted."""
    got = costs.per_launch(fleet_state, {}, tiny.APP, 256)
    assert tuple(got) == ("xlb_admit_commit", "xlb_complete")
    assert got["xlb_admit_commit"] == xlb_admit_commit.cost(
        fleet_state, {}, tiny.APP, 256)


def test_a_kernel_the_deployment_does_not_run_is_not_counted(tmp_path,
                                                             fleet_state):
    """A root's cost file of an expert kernel counts nothing for a dense
    application: its kernel is left out, the others counted as before."""
    d = tmp_path / "bench" / "costs"
    d.mkdir(parents=True)
    shutil.copy(STAND_IN / "xlb_experts.py", d)
    dense = {"blocks": {"attn": {}, "ffn": {}}}
    assert costs.per_launch(fleet_state, dense, tiny.APP, 256, tmp_path) \
        == costs.per_launch(fleet_state, dense, tiny.APP, 256)
    import jax
    import jax.numpy as jnp
    w = jax.ShapeDtypeStruct((2, 8, 64, 32), jnp.bfloat16)
    moe = {"blocks": {"moe": {"w_in": w, "w_gate": w, "w_out": w}}}
    got = costs.per_launch(fleet_state, moe, dict(tiny.APP, top_k=2), 256,
                           tmp_path)
    assert got["xlb_experts"] == (2 * 3 * 400 * 2 * 64 * 32 * 2,
                                  3 * 2 * 8 * 64 * 32 * 2)
    with pytest.raises(KeyError):
        costs.per_launch(fleet_state, moe, tiny.APP, 256, tmp_path)
