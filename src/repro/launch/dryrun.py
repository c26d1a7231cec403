import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run (deliverable e).

For every (architecture × input shape) cell, on the single-pod (16,16) mesh
AND the 2-pod (2,16,16) mesh:

    with mesh:
        lowered  = jax.jit(step, in_shardings=…, out_shardings=…,
                           donate_argnums=…).lower(**input_specs(arch, shape))
        compiled = lowered.compile()
        compiled.memory_analysis()   → proves the cell fits per-device HBM
        compiled.cost_analysis()     → HLO FLOPs/bytes for §Roofline
        compiled.as_text()           → collective schedule (parsed, not stored)

Results are cached as JSON under experiments/dryrun/ so the sweep is
resumable; `python -m repro.launch.dryrun --arch X --shape Y [--multi-pod]`
runs one cell, `--all` sweeps everything.

NOTE the first two lines of this file: jax locks the device count at first
init, and ONLY the dry-run should see 512 placeholder CPU devices.
"""

import argparse
import json
import re
import time
import traceback
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ASSIGNED_ARCHS, SHAPES, get_config, shape_applicable
from repro.launch.mesh import make_production_mesh
from repro.models import model as M
from repro.models.transformer import RunCtx
from repro.optim import adamw, schedules
from repro.roofline import analysis as RA
from repro.sharding.specs import MeshSpec

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                       "experiments", "dryrun")


def sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


# --------------------------------------------------------------------------- #
# Input specs (ShapeDtypeStruct stand-ins; zero allocation)
# --------------------------------------------------------------------------- #


def input_specs(cfg, shape) -> dict:
    """Abstract inputs for the step function of a given shape kind."""
    B, S = shape.global_batch, shape.seq_len
    if shape.kind == "train":
        batch = {"tokens": sds((B, S), jnp.int32),
                 "labels": sds((B, S), jnp.int32)}
        if cfg.is_encdec:
            batch["enc_frames"] = sds((B, cfg.enc_frames, cfg.d_model),
                                      jnp.float32)
        return {"batch": batch}
    if shape.kind == "prefill":
        out = {"tokens": sds((B, S), jnp.int32)}
        if cfg.is_encdec:
            out["enc_frames"] = sds((B, cfg.enc_frames, cfg.d_model),
                                    jnp.float32)
        return out
    # decode: one new token against a seq_len cache
    return {"token": sds((B, 1), jnp.int32),
            "lengths": sds((B,), jnp.int32)}


def abstract_state(cfg, shape, moment_dtype=jnp.float32):
    """Abstract params / optimizer / cache trees via eval_shape."""
    params = jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0)))
    out = {"params": params}
    if shape.kind == "train":
        out["opt"] = jax.eval_shape(partial(_init_opt, moment_dtype), params)
        out["bias"] = sds((max(cfg.moe.n_experts, 1),), jnp.float32)
    else:
        out["cache"] = jax.eval_shape(
            lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
    return out


def _init_opt(moment_dtype, params):
    z = lambda p: jnp.zeros(p.shape, moment_dtype)
    return adamw.AdamWState(step=jnp.zeros((), jnp.int32),
                            m=jax.tree.map(z, params),
                            v=jax.tree.map(z, params))


# --------------------------------------------------------------------------- #
# Step builders
# --------------------------------------------------------------------------- #


def make_ctx(cfg, ms: MeshSpec, shape, *, use_ep=True,
             explicit_fsdp=False) -> RunCtx:
    tok_axes = ms.dp + (ms.tp,)
    n_sh = 1
    for a in tok_axes:
        n_sh *= ms.mesh.shape[a]
    T = shape.global_batch * shape.seq_len
    ep = None
    if (use_ep and cfg.moe.enabled and shape.kind != "decode"
            and T * cfg.moe.top_k % n_sh == 0
            and cfg.moe.n_experts % ms.mesh.shape[ms.tp] == 0):
        ep = (ms.mesh, tok_axes)
    return RunCtx(shard=ms.constrain,
                  remat="block" if shape.kind == "train" else "none",
                  moe_method="sort", ep=ep,
                  tp_size=ms.mesh.shape[ms.tp],
                  explicit_fsdp=explicit_fsdp)


def build_train_step(cfg, ms, shape, moment_dtype, variant=""):
    ctx = make_ctx(cfg, ms, shape, explicit_fsdp=(variant == "exp_fsdp"))
    mb = 4 if variant.startswith("mb") else 0

    def train_step(params, opt_state, bias, batch):
        def loss_fn(p, b):
            return M.loss_fn(cfg, p, b, ctx=ctx)

        if mb:
            # gradient accumulation: trades activation memory for repeated
            # per-microbatch weight gathers (measured in the variant cell)
            B = batch["tokens"].shape[0]
            mbs = jax.tree.map(
                lambda a: a.reshape((mb, B // mb) + a.shape[1:]), batch)
            zeros = jax.tree.map(lambda q: jnp.zeros(q.shape, q.dtype), params)

            def micro(c, one):
                g_acc, l_acc, aux_prev = c
                (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, one)
                return (jax.tree.map(jnp.add, g_acc, g), l_acc + l, aux), None

            aux0 = jax.eval_shape(lambda: loss_fn(params, jax.tree.map(
                lambda a: a[0], mbs))[1])
            aux0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), aux0)
            (grads, ltot, aux), _ = jax.lax.scan(
                micro, (zeros, jnp.zeros(()), aux0), mbs)
            grads = jax.tree.map(lambda g: g / mb, grads)
            loss = ltot / mb
        else:
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
        lr = schedules.warmup_cosine(opt_state.step, warmup=100, total=10_000)
        params, opt_state, stats = adamw.apply(params, grads, opt_state,
                                               adamw.AdamWConfig(), lr)
        bias = adamw.update_router_bias(bias, aux["expert_load"])
        return params, opt_state, bias, {"loss": loss, **stats}

    return train_step, ctx


def build_prefill_step(cfg, ms, shape):
    ctx = make_ctx(cfg, ms, shape)

    def serve_prefill(params, cache, tokens, enc_frames=None):
        logits, cache = M.prefill(cfg, params, tokens, cache,
                                  enc_frames=enc_frames, ctx=ctx)
        return logits, cache

    return serve_prefill, ctx


def build_decode_step(cfg, ms, shape):
    ctx = make_ctx(cfg, ms, shape, use_ep=False)

    def serve_step(params, cache, token, lengths):
        logits, cache = M.decode_step(cfg, params, token, lengths, cache,
                                      ctx=ctx)
        return logits, cache

    return serve_step, ctx


# --------------------------------------------------------------------------- #
# One cell
# --------------------------------------------------------------------------- #


def lower_cell(arch: str, shape_name: str, multi_pod: bool,
               moment_dtype_str: str = "auto", variant: str = ""):
    """Lower + compile one (arch × shape × mesh) cell.

    ``variant``: hillclimb layouts — "serve_tp" = pure-TP serving params
    (replicated over dp; each dp slice is an XLB instance lane).
    Returns (compiled, lowered, report_dict).
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    if not ok:
        return None, None, {"skipped": reason}

    mesh = make_production_mesh(multi_pod=multi_pod)
    ms = MeshSpec(mesh, params_tp_only=(variant == "serve_tp"))
    # Moment dtype: bf16 for the two ≥200B trains on the single pod (fits in
    # 16 GB HBM; recorded in the report), fp32 otherwise.
    if moment_dtype_str == "auto":
        big = cfg.param_count() > 2e11 and not multi_pod
        moment_dtype = jnp.bfloat16 if big else jnp.float32
    else:
        moment_dtype = jnp.dtype(moment_dtype_str)

    state = abstract_state(cfg, shape, moment_dtype)
    inputs = input_specs(cfg, shape)
    p_sh = ms.params_shardings(state["params"])

    with mesh:
        if shape.kind == "train":
            fn, ctx = build_train_step(cfg, ms, shape, moment_dtype, variant)
            opt_sh = adamw.AdamWState(
                step=ms.named(jax.sharding.PartitionSpec()),
                m=jax.tree.map(lambda s: s, p_sh), v=jax.tree.map(lambda s: s, p_sh))
            bias_sh = ms.named(jax.sharding.PartitionSpec())
            batch_sh = ms.batch_shardings(inputs["batch"])
            jitted = jax.jit(fn,
                             in_shardings=(p_sh, opt_sh, bias_sh, batch_sh),
                             out_shardings=(p_sh, opt_sh, bias_sh, None),
                             donate_argnums=(0, 1))
            lowered = jitted.lower(state["params"], state["opt"],
                                   state["bias"], inputs["batch"])
        elif shape.kind == "prefill":
            fn, ctx = build_prefill_step(cfg, ms, shape)
            c_sh = ms.cache_shardings(cfg, state["cache"])
            tok_sh = ms.named(ms.batch_spec("tokens", inputs["tokens"].shape))
            args = [state["params"], state["cache"], inputs["tokens"]]
            in_sh = [p_sh, c_sh, tok_sh]
            if cfg.is_encdec:
                args.append(inputs["enc_frames"])
                in_sh.append(ms.named(ms.batch_spec(
                    "enc_frames", inputs["enc_frames"].shape)))
            jitted = jax.jit(fn, in_shardings=tuple(in_sh),
                             out_shardings=(None, c_sh), donate_argnums=(1,))
            lowered = jitted.lower(*args)
        else:
            fn, ctx = build_decode_step(cfg, ms, shape)
            c_sh = ms.cache_shardings(cfg, state["cache"])
            tok_sh = ms.named(ms.batch_spec("token", inputs["token"].shape))
            len_sh = ms.named(ms.batch_spec("lengths",
                                            inputs["lengths"].shape))
            jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, tok_sh, len_sh),
                             out_shardings=(None, c_sh), donate_argnums=(1,))
            lowered = jitted.lower(state["params"], state["cache"],
                                   inputs["token"], inputs["lengths"])

        t0 = time.perf_counter()
        compiled = lowered.compile()
        compile_s = time.perf_counter() - t0

    report = RA.analyze_compiled(cfg, shape, ms, compiled,
                                 multi_pod=multi_pod)
    report.update({
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "variant": variant or "baseline",
        "compile_s": round(compile_s, 1),
        "moment_dtype": str(jnp.dtype(moment_dtype)) if shape.kind == "train"
        else None,
        "ep_relay": ctx.ep is not None,
    })
    return compiled, lowered, report


# --------------------------------------------------------------------------- #
# Sweep driver (JSON-cached, resumable)
# --------------------------------------------------------------------------- #


def cell_path(arch, shape_name, multi_pod, variant=""):
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{variant}" if variant else ""
    return os.path.join(OUT_DIR, f"{arch}__{shape_name}__{mesh}{suffix}.json")


def run_cell(arch, shape_name, multi_pod, force=False, variant="") -> dict:
    path = cell_path(arch, shape_name, multi_pod, variant)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    print(f"=== dry-run {arch} × {shape_name} × "
          f"{'2x16x16' if multi_pod else '16x16'} {variant} ===", flush=True)
    try:
        compiled, lowered, report = lower_cell(arch, shape_name, multi_pod,
                                               variant=variant)
        if compiled is not None:
            print(compiled.memory_analysis())
            ca = compiled.cost_analysis()
            print({k: ca[k] for k in ("flops", "bytes accessed")
                   if k in ca})
    except Exception as e:
        report = {"arch": arch, "shape": shape_name,
                  "mesh": "2x16x16" if multi_pod else "16x16",
                  "error": f"{type(e).__name__}: {e}",
                  "trace": traceback.format_exc()[-2000:]}
        print(f"FAILED: {report['error']}", flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    return report


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="")
    args = ap.parse_args()

    cells = []
    archs = [args.arch] if args.arch else ASSIGNED_ARCHS
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    failures = 0
    for a, s, mp in cells:
        rep = run_cell(a, s, mp, force=args.force, variant=args.variant)
        if "error" in rep:
            failures += 1
    print(f"\n{len(cells)} cells, {failures} failures")
    return failures


if __name__ == "__main__":
    raise SystemExit(main())
