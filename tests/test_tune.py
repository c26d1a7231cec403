"""Autotuner contract (kernels/tune.py): env pins beat the sweep, explicit
arguments beat everything, XLB_AUTOTUNE=0 never times a candidate, and a
swept choice is cached (one sweep per (kernel, backend, shape))."""

import pytest

from repro.kernels import backend, tune


@pytest.fixture(autouse=True)
def _fresh_cache():
    tune.clear_cache()
    yield
    tune.clear_cache()


def _forbid_timing(monkeypatch):
    def boom(*a, **k):
        raise AssertionError("autotuner timed a candidate under a pin")
    monkeypatch.setattr(tune, "_time_best", boom)


def test_env_override_is_deterministic(monkeypatch):
    """The CI pin: with XLB_BLOCK_R/XLB_BLOCK_I/XLB_FOLD set, every plan is
    the pinned value, no candidate is ever timed, and repeated calls (even
    across cache clears) return the same plan."""
    monkeypatch.setenv(tune.ENV_AUTOTUNE, "1")
    monkeypatch.setenv(tune.ENV_BLOCK_R, "64")
    monkeypatch.setenv(tune.ENV_BLOCK_I, "2")
    monkeypatch.setenv(tune.ENV_FOLD, "onehot")
    _forbid_timing(monkeypatch)
    plans = set()
    for _ in range(3):
        tune.clear_cache()
        plans.add(tune.plan_admit(4096, (8, 64)))
        plans.add(tune.plan_admit(4096, (8, 64), commit=True))
        plans.add(tune.plan_complete((16, 256)))
    assert plans == {(64, "onehot"), (2, "onehot")}


def test_autotune_off_uses_static_defaults(monkeypatch):
    monkeypatch.setenv(tune.ENV_AUTOTUNE, "0")
    monkeypatch.delenv(tune.ENV_BLOCK_R, raising=False)
    monkeypatch.delenv(tune.ENV_BLOCK_I, raising=False)
    monkeypatch.delenv(tune.ENV_FOLD, raising=False)
    _forbid_timing(monkeypatch)
    br, fold = tune.plan_admit(4096, (8, 64))
    assert br == tune.DEFAULT_BLOCK_R
    assert fold == backend.default_fold()
    bi, _ = tune.plan_complete((16, 256))
    assert bi == min(16, tune.DEFAULT_BLOCK_I)
    # small batches clamp the default tile to the batch
    assert tune.plan_admit(32, (8, 64))[0] == 32


def test_explicit_args_outrank_env(monkeypatch):
    monkeypatch.setenv(tune.ENV_BLOCK_R, "64")
    monkeypatch.setenv(tune.ENV_FOLD, "onehot")
    _forbid_timing(monkeypatch)
    assert tune.plan_admit(4096, (8, 64), block_r=512,
                           fold="segment") == (512, "segment")
    assert tune.plan_complete((16, 256), block_i=4,
                              fold="segment") == (4, "segment")


def test_sweep_picks_fastest_and_caches(monkeypatch):
    """With autotune on and no pins: the sweep times each candidate once,
    picks the argmin, and the second identical call is a pure cache hit."""
    monkeypatch.setenv(tune.ENV_AUTOTUNE, "1")
    monkeypatch.delenv(tune.ENV_BLOCK_R, raising=False)
    calls = []

    def fake_time(fn, *a, **k):
        # deterministic fake timer: candidate identity is recoverable from
        # the sweep log, so just rank by insertion order — last wins
        calls.append(fn)
        return float(len(calls) % 7 == 3) + 1.0 / len(calls)

    monkeypatch.setattr(tune, "_time_best", fake_time)
    br1, fold1 = tune.plan_admit(1024, (4, 16))
    n_after_first = len(calls)
    assert n_after_first == len(tune._admit_candidates(1024)) > 1
    assert br1 in tune._admit_candidates(1024)
    br2, fold2 = tune.plan_admit(1024, (4, 16))
    assert (br1, fold1) == (br2, fold2)
    assert len(calls) == n_after_first          # cache hit: no re-timing
    # a different shape sweeps separately
    tune.plan_admit(256, (4, 16))
    assert len(calls) > n_after_first


def test_complete_candidates_divide_pool(monkeypatch):
    """Every candidate tiles the pool (the kernel pads the last tile); for
    a compiled backend only whole (8, 128) tiles or the whole pool are
    offered, so I = 50 never gets 2-row blocks."""
    for I in (1, 2, 6, 8, 16, 24, 50):
        for b in tune._complete_candidates(I):
            assert 1 <= b <= I and -(-I // b) * b - I < b
    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    for I in (1, 2, 6, 8, 16, 24, 50):
        cands = tune._complete_candidates(I)
        assert cands and all(b % 8 == 0 or b == I for b in cands), (I, cands)
    assert tune._complete_candidates(50) == [8, 16, 50]


def test_admit_candidates_tile_compiled_batches(monkeypatch):
    """Compiled admission tiles are whole 128-lane multiples or the whole
    batch, and fit VMEM: the interpreter-only 64-row tile and the
    1024-row tile are never offered."""
    monkeypatch.setattr(backend, "default_interpret", lambda: False)
    assert tune._admit_candidates(4096) == [256]
    assert tune._admit_candidates(1024) == [256]
    assert tune._admit_candidates(256) == [256]
    assert tune._admit_candidates(32) == [32]
    monkeypatch.setattr(backend, "default_interpret", lambda: True)
    assert tune._admit_candidates(1024) == [64, 256, 1024]


def test_sweep_raises_on_a_failing_candidate(monkeypatch):
    """A candidate that fails to compile aborts the sweep: it is never
    skipped in favour of the others."""
    monkeypatch.setenv(tune.ENV_AUTOTUNE, "1")

    def make_fn(b):
        def fn():
            if b == 2:
                raise RuntimeError("refused by the compiler")
            return b
        return fn

    with pytest.raises(RuntimeError, match="refused"):
        tune._sweep(("probe",), [1, 2, 3], make_fn)
    assert ("probe",) not in tune._cache


def test_fold_validation():
    with pytest.raises(ValueError):
        backend.resolve_fold("bogus")
    assert backend.resolve_fold(None) in backend.FOLDS
