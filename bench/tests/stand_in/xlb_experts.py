"""A stand-in cost of an expert kernel: the routed experts' three matmuls
for ``top_k`` experts a token over every pool slot, reading the held
experts' weights once.  A deployment whose application holds no routed
experts runs no such kernel."""

import math

from bench.costs import nbytes


def cost(state, params, app: dict, admit_batch: int, fold=None):
    moe = params.get("blocks", {}).get("moe")
    if moe is None:
        return None
    layers, _, d_model, d_expert = moe["w_in"].shape
    slots = math.prod(state.pool.req_id.shape)
    ops = 2 * 3 * slots * app["top_k"] * d_model * d_expert * layers
    moved = nbytes([moe["w_in"], moe["w_gate"], moe["w_out"]])
    return float(ops), float(moved)
