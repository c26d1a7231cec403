"""The applications' plain references, one module per kind found by the
configuration's ``reference``: the dense one against the program's decode,
its FLOPs by hand, and a non-dense application with its reference and a
kernel's cost deployed from new files only."""

import dataclasses
import json
import shutil
from pathlib import Path

import jax.numpy as jnp
import numpy as np
import pytest

from bench import apps, check, deploy, reference, run
from bench.apps import dense
from bench.tests import tiny

STAND_IN = Path(__file__).resolve().parent / "stand_in"


def test_a_configuration_without_a_reference_is_dense():
    from repro.configs import get_config
    cfg = get_config(tiny.APP["name"])
    assert apps.for_application(tiny.APP) is dense
    dense.check(tiny.APP, cfg)
    with pytest.raises(ValueError, match="family"):
        dense.check(tiny.APP, dataclasses.replace(cfg, family="moe"))
    with pytest.raises(ValueError, match="d_ff"):
        dense.check(dict(tiny.APP, d_ff=512), cfg)


def test_forward_matches_the_program_decode():
    """The full-sequence reference against the program's decode step fed
    token by token through its cache (float32 on the CPU)."""
    from repro.configs import get_config
    from repro.models import model as M
    cfg = get_config(tiny.APP["name"])
    params = deploy.make_params(deploy.param_shapes(cfg, jnp.float32), 3)
    toks = np.random.default_rng(0).integers(2, 512, (3, 6)).astype(np.int32)
    cache = M.init_cache(cfg, 3, 8, jnp.float32)
    steps = []
    for p in range(6):
        lg, cache = M.decode_step(cfg, params, jnp.asarray(toks[:, p:p + 1]),
                                  jnp.full((3,), p, jnp.int32), cache)
        steps.append(np.asarray(lg))
    want = np.stack(steps, 1)
    got = np.asarray(dense.forward_logits(tiny.APP, params,
                                          jnp.asarray(toks)))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=1e-4)
    gaps = reference.served_gaps(got[:, :-1], want[:, :-1].argmax(-1))
    assert gaps.max() < 1e-4


def test_decode_flops_by_hand():
    app = tiny.APP
    # per layer: q 128x128, k and v 128x64 each, o 128x128, swiglu 3x128x256
    per_layer = 128 * 128 + 2 * 128 * 64 + 128 * 128 + 3 * 128 * 256
    matmul = 2 * 400 * (2 * per_layer + 128 * 512)
    attn = 4 * 400 * 2 * 4 * 32 * 16
    assert dense.decode_flops(app, 400, 16) == matmul + attn


def _smoke_moe_root(root: Path, monkeypatch) -> Path:
    """A root that holds only new files: the tiny fleet serving the
    program's smoke configuration of DeepSeek-V2 (registered for the test
    under its own name), its stand-in reference and an expert kernel's
    cost."""
    from repro.configs import base, get_config, smoke_config
    cfg = smoke_config(get_config("deepseek-v2-236b"))
    monkeypatch.setitem(base._REGISTRY, cfg.name, cfg)
    tiny.write_root(root)
    app = {"name": cfg.name, "reference": "smoke_moe",
           **{k: getattr(cfg, k) for k in ("n_layers", "d_model", "n_heads",
                                           "n_kv_heads", "head_dim", "d_ff",
                                           "vocab")},
           "top_k": cfg.moe.top_k, "dtype": "bfloat16"}
    config = dict(tiny.CONFIG, application=app)
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(config))
    for kind, name in (("apps", "smoke_moe"), ("costs", "xlb_experts")):
        (root / "bench" / kind).mkdir(parents=True)
        shutil.copy(STAND_IN / f"{name}.py", root / "bench" / kind)
    return root


def test_a_new_application_arrives_as_new_files(tmp_path, monkeypatch,
                                                capsys):
    root = _smoke_moe_root(tmp_path, monkeypatch)
    result, numbers = run.execute("tiny.tiny-closed", tiny.SEED, 1.0, False,
                                  root=root, require_chip=False)
    assert result["attempted"] > 20 and result["failed"] == 0
    # every exact number holds; the stand-in's logit gap (the program's
    # bfloat16 decode against its own float32 forward) has no limit of its
    # own here
    assert all(numbers[k][0] == 0 for k in check.EXACT), numbers
    assert np.isfinite(numbers["logit_gap"][0])
    err = capsys.readouterr().err
    # the root's expert kernel is counted beside the harness's two, from
    # the application's widths: 8 x 2 slots, 2 experts a token, d_model
    # 64, experts 32 wide, one routed layer; 4 held experts' bfloat16
    # weights
    ops = 2 * 3 * 16 * 2 * 64 * 32 * 1
    assert f"'xlb_experts': ({ops}.0, {3 * 4 * 64 * 32 * 2}.0)" in err
    assert "'xlb_admit_commit'" in err
