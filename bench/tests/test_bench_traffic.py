"""The traffic generator: identical for a seed, different across seeds,
the same amount of work for every seed."""

import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic import TEMPLATES, Mix

TRAFFIC = Path(__file__).resolve().parents[1] / "traffic"
BIG = 2**31 + 5              # seeds run past 32 signed bits


def _mix(name, seed):
    return Mix(json.loads((TRAFFIC / f"{name}.json").read_text()), seed, 512)


def _cols(r):
    return np.stack([r.cls, r.user, r.token, r.msg_bytes])


@pytest.mark.parametrize("name", ["session-zipf", "rpc-closed"])
def test_same_seed_same_requests(name):
    a, b = _mix(name, BIG), _mix(name, BIG)
    if a.loop == "open":
        (da, ra), (db, rb) = a.schedule(10.0), b.schedule(10.0)
        np.testing.assert_array_equal(da, db)
        np.testing.assert_array_equal(_cols(ra), _cols(rb))
    else:
        np.testing.assert_array_equal(_cols(a.templates()),
                                      _cols(b.templates()))


@pytest.mark.parametrize("name", ["session-zipf", "rpc-closed"])
def test_other_seed_other_requests(name):
    a, b = _mix(name, BIG), _mix(name, BIG + 1)
    if a.loop == "open":
        (da, ra), (db, rb) = a.schedule(10.0), b.schedule(10.0)
        assert not np.array_equal(da, db)
    else:
        ra, rb = a.templates(), b.templates()
    assert not np.array_equal(_cols(ra), _cols(rb))


def test_open_loop_offers_the_same_count_for_every_seed():
    counts = {len(_mix("session-zipf", s).schedule(10.0)[0])
              for s in (1, 2, BIG, 2**40)}
    m = _mix("session-zipf", 1)
    assert counts == {round(m.spec["rate_rps"] * (m.warmup_s + 10.0))}
    due, _ = m.schedule(10.0)
    assert np.all(np.diff(due) >= 0) and 0 <= due[0] and due[-1] < 11.0


def test_zipf_mix_shares():
    m = _mix("session-zipf", 3)
    r = m.draw(200_000, 9)
    jason = m.user_names.index("jason")
    assert abs(np.mean(r.user == jason) - 0.05) < 0.003
    # rank-1 user of a Zipf(1.0) over 10,000 users, scaled by the 95% left
    h = np.sum(1.0 / np.arange(1, 10_001))
    assert abs(np.mean(r.user == 0) - 0.95 / h) < 0.004
    share = np.bincount(r.cls, minlength=4) / r.cls.size
    np.testing.assert_allclose(share, np.array([1, 1, 1, 0.525]) / 3.525,
                               atol=0.004)
    assert r.token.min() >= 2 and r.token.max() < 512


def test_headers_carry_the_user():
    m = _mix("session-zipf", 3)
    h = m.headers(3, m.user_names.index("jason"))
    assert h == {"path": "/ratings", "user": "jason"}


def test_closed_loop_clients_and_templates():
    m = _mix("rpc-closed", 4)
    assert m.clients(400) == 400
    idx = {m.template_index(c, k) for c in range(400) for k in range(50)}
    assert len(idx) > 0.7 * 400 * 50 and max(idx) < TEMPLATES
