"""``chip_smoke.py`` rehearsed on the CPU at small sizes: its phases pass
on a correct datapath, its compiled-kernel check is not vacuous, and the
script refuses to report a result without a TPU.  Also the one compile-
cache placement rule every entry point shares (``launch/cache.py``)."""

import importlib.util
import os

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def no_cache(monkeypatch):
    """Keep the persistent-cache setting of this test process untouched."""
    monkeypatch.setattr("repro.launch.cache.setup_compile_cache",
                        lambda: "<unset>")


def test_refuses_without_a_tpu(smoke, no_cache, capsys):
    assert smoke.main([]) == 1
    out = capsys.readouterr()
    assert out.out == "" and "no TPU" in out.err


def test_run_phases_reports_every_failure(smoke, capsys):
    """A failed phase does not stop the later ones, and is reported."""
    ran = []

    def boom():
        raise AssertionError("phase broke")

    failed = smoke.run_phases([("a", boom), ("b", lambda: ran.append("b"))])
    assert failed == ["a"] and ran == ["b"]
    assert "phase broke" in capsys.readouterr().err


@pytest.mark.parametrize("fold", ["segment", "onehot"])
def test_oracle_phase_passes(smoke, monkeypatch, fold):
    monkeypatch.setenv("XLB_FOLD", fold)
    smoke.check_oracle(6, 4, 32, max_len=8)


def test_oracle_phase_catches_a_wrong_pool(smoke):
    """The comparison helpers fail loudly on a one-cell difference."""
    import numpy as np
    a = np.zeros((3, 4), np.int32)
    b = a.copy()
    b[1, 2] = 5
    with pytest.raises(AssertionError, match="1 mismatched"):
        smoke._same("pool", a, b)
    with pytest.raises(AssertionError, match="relative"):
        smoke._close("ewma", np.ones(3), np.ones(3) * (1 + 1e-5), 1e-6)


@pytest.mark.parametrize("engine", ["xlb", "istio", "cilium"])
def test_serve_phase_completes(smoke, engine):
    run = smoke.serve_engine(engine, instances=3, slots=2, admit_batch=8,
                             requests=10, max_len=6)
    assert len(run.report.done) == 10


def test_compiled_check_rejects_interpret_mode(smoke):
    """On the CPU the kernels run interpreted: serve_step holds no
    tpu_custom_call, and the chip-side check must say so."""
    from repro.launch import serve
    args = smoke._serve_args("xlb", instances=2, slots=2, admit_batch=8,
                             requests=1, max_len=6)
    _, loop = serve.build(args)
    with pytest.raises(AssertionError, match="0 tpu_custom_call"):
        smoke.check_compiled(loop)


def test_compile_cache_placement(monkeypatch):
    from repro.launch import cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv(cache.ENV, "/some/where")
    assert cache.setup_compile_cache() == "/some/where"
    assert calls == []                       # JAX reads the variable itself
    monkeypatch.delenv(cache.ENV)
    path = cache.setup_compile_cache()
    assert calls == [("jax_compilation_cache_dir", path)]
    assert path == os.path.join(ROOT, ".jax_cache")
