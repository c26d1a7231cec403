"""Operations and bytes of one launch of each kernel on the serving path,
counted from shapes: one module per kernel, ``bench/costs/<kernel>.py``,
whose file name is the kernel's name in the trace (an op named
``<kernel>`` or ``<kernel>.<n>``, ``bench/trace.py::extract``).

Each module exports ``cost(state, params, app, admit_batch, fold=None)``:
(operations, bytes) of one launch at the live engine state's sizes, the
served application's parameters and its configuration entry, with
admission batches of ``admit_batch`` rows, the kernel's results taken from
the program's own wrappers traced abstractly (``fold`` None: the
backend's); or None where the deployment runs no such kernel.

Bytes are every operand and every result of a kernel, each once: what the
kernel must at least move between HBM and the core, whichever fold
implements it.  Operations are what the algorithm needs, not what a fold
spends.
"""

from __future__ import annotations

import math
from pathlib import Path

import jax
import numpy as np

from bench import registry


def nbytes(tree) -> int:
    return sum(math.prod(x.shape) * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def shapes(tree):
    """The tree's arrays as shapes and dtypes only."""
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                        tree)


def per_launch(state, params, app: dict, admit_batch: int,
               root: Path = registry.ROOT) -> dict:
    """Kernel name → (operations, bytes) of one launch, for every kernel of
    ``bench/costs`` that this deployment runs."""
    out = {}
    for name in registry.names("costs", root):
        c = registry.load("costs", name, root).cost(state, params, app,
                                                    admit_batch)
        if c is not None:
            out[name] = c
    return out
