"""The datapath verifier (src/repro/analysis/): mutation tests — every
seeded defect must be rejected with its named diagnostic — plus the
clean-pass sweep over every registered policy × fold, the plan-law and
row-schema validators, the sanitizer, and regressions for the OOB bugs
the static pass originally surfaced (route_match svc clamp, relay
sentinel rank, policies cluster clip, delta empty-window removal)."""

from __future__ import annotations

import ast

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import lint as lint_mod
from repro.analysis import verifier as ver
from repro.analysis.invariants import (assert_host, check_plan_wire, guard,
                                       validate_row)
from repro.analysis.verifier import Interval, verify_fn


def _codes(findings):
    return {f.code for f in findings}


# --------------------------------------------------------------------------- #
# Mutation tests: each seeded defect is rejected with a named diagnostic.
# --------------------------------------------------------------------------- #


def test_mutation_unclamped_gather_is_rejected():
    t = jnp.zeros((50,), jnp.int32)
    i = jnp.zeros((8,), jnp.int32)
    out = verify_fn(lambda t, i: t[i], (t, i), name="mut")
    assert _codes(out) == {"oob-gather-bound"}


def test_mutation_wide_clamp_gather_is_rejected():
    # clamped — but against the WRONG bound (table has 50 rows, clamp to 100)
    t = jnp.zeros((50,), jnp.int32)
    i = jnp.zeros((8,), jnp.int32)
    out = verify_fn(lambda t, i: t[jnp.clip(i, 0, 100)], (t, i), name="mut")
    assert _codes(out) == {"oob-gather-bound"}
    # the same gather with the right clamp is proven clean
    ok = verify_fn(lambda t, i: t[jnp.clip(i, 0, 49)], (t, i), name="ok")
    assert ok == []


def test_mutation_promise_scatter_is_rejected():
    t = jnp.zeros((50,), jnp.int32)
    i = jnp.zeros((8,), jnp.int32)
    out = verify_fn(
        lambda t, i: t.at[i].set(1, mode="promise_in_bounds"),
        (t, i), name="mut")
    assert _codes(out) == {"oob-scatter-bound"}
    # an explicit drop mode needs no proof (and the entry-bounds path
    # proves the promise form once the caller declares the index range)
    ok = verify_fn(lambda t, i: t.at[i].set(1, mode="drop"), (t, i),
                   name="ok")
    assert ok == []
    ok2 = verify_fn(
        lambda t, i: t.at[i].set(1, mode="promise_in_bounds"),
        (t, i), bounds=[None, Interval(0, 49)], name="ok2")
    assert ok2 == []


def test_mutation_unclamped_ref_index_is_rejected():
    from jax.experimental import pallas as pl

    def kern(i_ref, t_ref, o_ref):
        o_ref[0] = t_ref[i_ref[0]]        # raw dynamic ref index

    def mut(i, t):
        return pl.pallas_call(
            kern, out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
            interpret=True)(i, t)

    i = jnp.zeros((1,), jnp.int32)
    t = jnp.zeros((50,), jnp.int32)
    out = verify_fn(mut, (i, t), name="mut")
    assert "unclamped-ref-index" in _codes(out)

    def kern_ok(i_ref, t_ref, o_ref):
        o_ref[0] = t_ref[jnp.clip(i_ref[0], 0, 49)]

    def fixed(i, t):
        return pl.pallas_call(
            kern_ok, out_shape=jax.ShapeDtypeStruct((1,), jnp.int32),
            interpret=True)(i, t)

    assert verify_fn(fixed, (i, t), name="ok") == []


def test_mutation_x64_promotion_is_rejected():
    with jax.enable_x64(True):
        x = jnp.zeros((4,), jnp.float64)
        out = verify_fn(lambda a: a * 2.0, (x,), name="mut")
    assert "x64-promotion" in _codes(out)


def test_mutation_rng_prim_is_rejected():
    def mut(x):
        return x + jax.lax.rng_uniform(0.0, 1.0, (8,)).astype(jnp.int32)

    out = verify_fn(mut, (jnp.zeros((8,), jnp.int32),), name="mut")
    assert "rng-in-datapath" in _codes(out)


def test_mutation_registry_missing_hook_is_rejected(monkeypatch):
    import dataclasses

    from repro.core import policy_defs

    broken = dataclasses.replace(policy_defs.REGISTRY[0], name="mut",
                                 enum=99, kernel_offset=None)
    monkeypatch.setattr(policy_defs, "REGISTRY",
                        policy_defs.REGISTRY + (broken,))
    assert "policy-missing-hook" in _codes(ver.check_registry())


def test_mutation_registry_bad_merge_is_rejected(monkeypatch):
    import dataclasses

    from repro.core import policy_defs

    broken = dataclasses.replace(policy_defs.REGISTRY[0], name="mut",
                                 enum=99, shard_merge="psum")
    monkeypatch.setattr(policy_defs, "REGISTRY",
                        policy_defs.REGISTRY + (broken,))
    assert "policy-bad-merge" in _codes(ver.check_registry())


def test_mutation_registry_dup_enum_is_rejected(monkeypatch):
    import dataclasses

    from repro.core import policy_defs

    dup = dataclasses.replace(policy_defs.REGISTRY[1], name="mut")
    monkeypatch.setattr(policy_defs, "REGISTRY",
                        policy_defs.REGISTRY + (dup,))
    assert "policy-dup-enum" in _codes(ver.check_registry())


# --------------------------------------------------------------------------- #
# Plan-law mutations (check_plan_wire names the violated law).
# --------------------------------------------------------------------------- #


@pytest.fixture()
def clean_wire():
    from repro.core import control

    cp = control.ControlPlane()
    cp.add_cluster("a", endpoints=[0, 1, 2])
    cp.add_cluster("b", endpoints=[3, 4])
    wire = dict(cp.journal[-1])
    assert check_plan_wire(wire) == []
    return wire


def test_mutation_plan_field_bounds(clean_wire):
    from repro.core.routing_table import MAX_EPS_PER_CLUSTER

    clean_wire["cluster_ep_count"] = np.array(
        clean_wire["cluster_ep_count"]).copy()
    clean_wire["cluster_ep_count"][0] = MAX_EPS_PER_CLUSTER + 7
    errs = check_plan_wire(clean_wire)
    assert any(e.startswith("[field-bounds]") for e in errs)


def test_mutation_plan_window_overlap(clean_wire):
    cs = np.array(clean_wire["cluster_ep_start"]).copy()
    cs[1] = cs[0]                       # cluster b's window over cluster a's
    clean_wire["cluster_ep_start"] = cs
    errs = check_plan_wire(clean_wire)
    assert any(e.startswith("[window-disjoint]") for e in errs)


def test_mutation_plan_broken_permutation(clean_wire):
    src = np.array(clean_wire["ep_src"]).copy()
    dst = np.array(clean_wire["ep_dst"]).copy()
    src[0], dst[1] = 1, 5               # dst[src[0]] != 0
    clean_wire["ep_src"], clean_wire["ep_dst"] = src, dst
    errs = check_plan_wire(clean_wire)
    assert any(e.startswith("[slot-permutation]") for e in errs)


def test_mutation_plan_version_regression(clean_wire):
    clean_wire["base_version"] = clean_wire["version"]
    errs = check_plan_wire(clean_wire)
    assert any(e.startswith("[version-monotone]") for e in errs)


def test_unpack_plan_rejects_mutated_wire(clean_wire):
    from repro.core import control

    clean_wire["rule_cluster"] = np.array(clean_wire["rule_cluster"]).copy()
    clean_wire["rule_cluster"][0] = 10_000
    with pytest.raises(ValueError, match="violates invariants"):
        control.unpack_plan(clean_wire)


# --------------------------------------------------------------------------- #
# AST-lint mutations on synthetic sources.
# --------------------------------------------------------------------------- #


def _lint_src(src, mod="repro.kernels.mut"):
    findings = []
    lint_mod._ModuleLinter(mod, findings).visit(ast.parse(src))
    return findings


def test_mutation_lint_scatter_missing_mode():
    out = _lint_src("y = t.at[i].set(v)\n")
    assert _codes(out) == {"scatter-missing-mode"}
    assert _lint_src("y = t.at[i].set(v, mode='drop')\n") == []
    assert _lint_src("y = t.at[0].set(v)\n") == []    # static index: safe


def test_mutation_lint_nondet_in_datapath():
    assert _codes(_lint_src("x = np.random.rand(4)\n")) \
        == {"nondet-in-datapath"}
    assert _codes(_lint_src("t0 = time.perf_counter()\n")) \
        == {"nondet-in-datapath"}
    assert _lint_src("g = np.random.default_rng(0)\n") == []


def test_mutation_lint_enum_literal_bypass():
    out = _lint_src("ok = policy == 3\n")
    assert _codes(out) == {"enum-literal-bypass"}
    assert _lint_src("ok = policy == policy_defs.POLICY_MAGLEV\n") == []
    assert _lint_src("ok = policy < n_policies\n") == []  # range guard


def test_mutation_lint_partial_policydef():
    src = "P = PolicyDef('x', 9, (), (), 'none', kernel_offset=f)\n"
    assert _codes(_lint_src(src)) == {"policy-missing-hook"}


# --------------------------------------------------------------------------- #
# Row-schema mutations.
# --------------------------------------------------------------------------- #


def test_mutation_scenario_row_rejected():
    from repro.workload import slo

    row = slo.scenario_row("s", "xlb", depth=1, seed=0, arrivals="poisson",
                           n_requests=4, completed=4, dropped=0, ticks=9,
                           samples=[1, 2, 3, 4])
    bad = dict(row)
    bad["completed"] = 9                    # completed + dropped > n_requests
    with pytest.raises(ValueError, match="exceeds n_requests"):
        validate_row(bad, "scenario")
    bad2 = dict(row)
    bad2["surprise"] = 1
    with pytest.raises(ValueError, match="unknown field"):
        validate_row(bad2, "scenario")


# --------------------------------------------------------------------------- #
# Clean pass: every registered policy × fold, zero findings on HEAD.
# --------------------------------------------------------------------------- #


def test_registry_clean():
    assert ver.check_registry() == []


def test_kernel_sweep_clean_all_policies_both_folds():
    # _sweep_state builds one live cluster per REGISTRY policy, so this
    # single sweep proves every registered policy under every lowering the
    # kernels trace, across both folds.
    assert ver.verify_kernels(folds=("segment", "onehot")) == []


def test_lint_clean():
    report, findings = lint_mod.lint_all()
    assert findings == []
    # the import report flags the seed's dead training modules but the
    # datapath must never import them
    assert "repro.runtime.train_loop" in report["dead"]
    assert "repro.kernels.route_match" in report["datapath"]


def test_plan_op_sweep_clean():
    from repro.analysis.__main__ import _plan_ops_findings

    assert _plan_ops_findings() == []


# --------------------------------------------------------------------------- #
# Sanitizer: laws hold on real outputs, violations raise with the law name.
# --------------------------------------------------------------------------- #


def test_guard_passes_on_lawful_ctx():
    guard("admit", dict(load_before=jnp.zeros((4,), jnp.int32),
                        load_after=jnp.array([1, 1, 0, 0], jnp.int32),
                        ok=jnp.array([1, 1, 0, 0], jnp.int32),
                        held=jnp.int32(0),
                        endpoint=jnp.array([0, 1, -1, -1], jnp.int32)))


def test_guard_rejects_load_leak():
    from jax._src.checkify import JaxRuntimeError

    with pytest.raises(JaxRuntimeError,
                       match="load-delta-conservation"):
        guard("admit", dict(load_before=jnp.zeros((4,), jnp.int32),
                            load_after=jnp.array([2, 1, 0, 0], jnp.int32),
                            ok=jnp.array([1, 1, 0, 0], jnp.int32),
                            held=jnp.int32(0),
                            endpoint=jnp.array([0, 1, -1, -1], jnp.int32)))


def test_assert_host_rejects_queue_leak():
    with pytest.raises(AssertionError, match="queue-conservation"):
        assert_host("loop", dict(submitted=5, done=2, dropped=0, queued=1,
                                 inflight=1))
    assert_host("loop", dict(submitted=4, done=2, dropped=0, queued=1,
                             inflight=1))


def test_sanitized_serve_loop_runs(monkeypatch):
    monkeypatch.setenv("XLB_SANITIZE", "1")
    from repro.configs import get_config, smoke_config
    from repro.core import control, interpose
    from repro.core.routing_table import POLICY_RR
    from repro.models import model as M
    from repro.runtime.serve_loop import Request, ServeLoop

    cfg = smoke_config(get_config("xlb-service-model"))
    params = M.init_params(cfg, jax.random.PRNGKey(7), dtype=jnp.float32)
    cp = control.ControlPlane()
    cp.add_cluster("c", policy=POLICY_RR, endpoints=[0, 1])
    cp.add_service("s", rules=[control.Rule(0, None, "c")])
    eng = interpose.Engine(cfg, 2, 2, 8)
    loop = ServeLoop(eng, params, cp, admit_batch=4)
    for r in range(4):
        loop.submit(Request(req_id=r, service=0, headers={}, prompt_token=2))
    rep = loop.drain(max_ticks=200)
    assert len(rep.done) == 4
    assert loop.submitted == 4


# --------------------------------------------------------------------------- #
# Regressions for the audit findings the verifier surfaced (now fixed).
# --------------------------------------------------------------------------- #


def _two_cluster_state():
    from repro.core.routing_table import (Cluster, Rule, ServiceConfig,
                                          build_state)

    state, _ = build_state(
        [ServiceConfig("s0", rules=[Rule(0, None, "a")])],
        [Cluster("a", endpoints=[0, 1, 2]), Cluster("b", endpoints=[3, 4])])
    return state


def test_route_match_out_of_range_service_matches_clamped():
    # the kernel once read the rule tables with a raw svc id — an id past
    # MAX_SERVICES walked other services' rule windows once compiled
    from repro.core.routing_table import MAX_SERVICES
    from repro.kernels import ops

    state = _two_cluster_state()
    feats = jnp.zeros((4, 8), jnp.int32)
    hot = jnp.array([0, MAX_SERVICES - 1, MAX_SERVICES + 17, 2**30],
                    jnp.int32)
    ref = jnp.full((4,), MAX_SERVICES - 1, jnp.int32)
    cl_hot, _ = ops.route_match(hot, feats, state)
    cl_ref, _ = ops.route_match(ref, feats, state)
    np.testing.assert_array_equal(np.asarray(cl_hot[1:]),
                                  np.asarray(cl_ref[1:]))


def test_positions_sort_sentinel_destination_is_safe():
    # shard_admit steers dropped rows to destination == n_dest; the rank
    # gather once read starts[n_dest] out of bounds.  Sentinel rows must
    # not disturb the ranks of real rows.
    from repro.core import relay

    n = 4
    idx = jnp.array([0, n, 2, n, 0, 2], jnp.int32)
    slot, load = jax.jit(relay.positions_sort, static_argnums=1)(idx, n)
    slot = np.asarray(slot)
    assert list(np.asarray(load)) == [2, 0, 2, 0]
    assert slot[0] == 0 and slot[4] == 1          # dest-0 arrival ranks
    assert slot[2] == 0 and slot[5] == 1          # dest-2 arrival ranks


def test_policies_select_clips_out_of_range_cluster():
    # select once only lower-clamped the cluster id: an id past the table
    # walked cluster_ep_start/count out of window
    from repro.core import policies

    state = _two_cluster_state()
    n_cl = state.cluster_ep_start.shape[0]
    key = jax.random.PRNGKey(0)
    sel_oob, _ = policies.select(state,
                                 jnp.array([n_cl + 7], jnp.int32), key)
    sel_last, _ = policies.select(state,
                                  jnp.array([n_cl - 1], jnp.int32), key)
    assert int(sel_oob.endpoint[0]) == int(sel_last.endpoint[0])
    E = state.ep_instance.shape[0]
    assert -1 <= int(sel_oob.endpoint[0]) < E


def test_remove_endpoint_from_empty_cluster_is_noop():
    # a raced double-remove once drove count negative and let the
    # last-slot swap (last = start - 1) corrupt the neighbouring cluster
    from repro.core import delta

    state = _two_cluster_state()
    st = delta.remove_endpoint(state, 1, 0)       # b: 2 eps -> 1
    st = delta.remove_endpoint(st, 1, 0)          # b: 1 ep  -> 0
    before = jax.tree.map(np.asarray, st)
    st2 = delta.remove_endpoint(st, 1, 0)         # b already empty
    assert int(st2.cluster_ep_count[1]) == 0
    assert int(st2.version) == int(st.version) + 1
    for name in ("ep_instance", "ep_load", "ep_weight", "ep_drained",
                 "cluster_ep_start", "cluster_ep_count"):
        np.testing.assert_array_equal(np.asarray(getattr(st2, name)),
                                      getattr(before, name), err_msg=name)
    # cluster a untouched throughout
    np.testing.assert_array_equal(np.asarray(st2.ep_instance[:3]),
                                  np.asarray(state.ep_instance[:3]))
