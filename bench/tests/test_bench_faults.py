"""With the timed path broken underneath, a run's ``correct`` comes out
false: a step that returns its state unchanged, half of the admission
batch left out, and a token altered where it is produced.  (The cells run
on one chip, so no exchange between chips exists to leave out.)"""

import jax.numpy as jnp
import pytest

from bench import driver, run
from bench.tests import tiny


def state_unchanged(step):
    def broken(params, state, reqs):
        _, out = step(params, state, reqs)
        return state, out
    return broken


def half_batch(step):
    def broken(params, state, reqs):
        R = reqs.req_id.shape[0]
        keep = jnp.arange(R) < R // 2
        return step(params, state,
                    reqs._replace(req_id=jnp.where(keep, reqs.req_id, -1)))
    return broken


def token_altered(step):
    def broken(params, state, reqs):
        new, out = step(params, state, reqs)
        act = new.pool.active
        tok = jnp.where(act, (new.pool.token + 7) % 512, new.pool.token)
        out = dict(out, emitted=jnp.where(state.pool.active | act,
                                          (out["emitted"] + 7) % 512,
                                          out["emitted"]))
        return new._replace(pool=new.pool._replace(token=tok)), out
    return broken


@pytest.mark.parametrize("fault,caught", [
    (state_unchanged, "unfinished"),
    (half_batch, "tick_mismatches"),
    (token_altered, "logit_gap"),
])
def test_a_broken_step_is_not_correct(tmp_path, monkeypatch, fault, caught):
    monkeypatch.setattr(driver, "GRACE_S", 3.0)    # a wedged run's wait
    root = tiny.write_root(tmp_path)
    result, numbers = run.execute("tiny.tiny-closed", tiny.SEED, 1.0, False,
                                  root=root, require_chip=False,
                                  fault=fault)
    assert result["correct"] is False
    value, limit = numbers[caught]
    assert value > limit, numbers
