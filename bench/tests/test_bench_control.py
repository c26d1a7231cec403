"""The control at a size a test can hold: with the plain reference put in
the program's place one precision below the configuration's (fp8 for its
bfloat16), the harness's own comparison judges the run not correct, on a
logit gap above the limit, where the program's own gap on the same
requests lies below it."""

from bench import check, control
from bench.tests import tiny


def test_the_control_fails_the_logit_gap(tmp_path):
    root = tiny.write_root(tmp_path)
    r = control.readings("tiny.tiny-closed", tiny.SEED, 1.0, root=root,
                         require_chip=False)
    limit = check.limits_for(tiny.CONFIG)["logit_gap"]
    assert r["tokens"] > 200
    assert r["program"]["logit_gap"] <= limit < r["control"]["logit_gap"], r
    assert r["control_correct"] is False
