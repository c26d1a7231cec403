"""Backend-aware execution defaults shared by every Pallas kernel.

Off-TPU (CPU CI, local runs) the kernels execute through the Pallas
interpreter — bit-accurate against the BlockSpec pipeline; on a real TPU
backend they compile through Mosaic.  Callers pass ``interpret=None`` to
get the auto-selected mode, or force a bool explicitly (tests, debugging).

The same split selects the *aggregation strategy* of the datapath kernels
(DESIGN.md §5).  ``fold="onehot"`` is the form Mosaic compiles: table reads
are one-hot compare/select reductions or lane barrel shifts, running counts
are 0/1 bf16 triangular matmuls with f32 accumulation, and 2-D row reads
and pool writes are one-hot matmuls over 8-bit planes — no gather,
scatter, sort, cumsum or 2-D → 1-D reshape inside a kernel.
``fold="segment"`` keeps gathers, scatter-adds and a sort, which only the
interpreter runs, in linear time.  ``tests/test_tpu_compile.py`` compiles
the onehot datapath kernels for a described v5e at serving shapes.
``resolve_fold`` picks per backend; the block-size autotuner
(``kernels/tune.py``) can override both the fold and the tile shapes.
"""

from __future__ import annotations

import jax

FOLDS = ("onehot", "segment")


def backend_kind() -> str:
    """The cache/tuning key: 'tpu' | 'gpu' | 'cpu' (anything else verbatim)."""
    return jax.default_backend()


def default_interpret() -> bool:
    """True when the default backend cannot compile Mosaic kernels."""
    return backend_kind() != "tpu"


def resolve_interpret(interpret: bool | None) -> bool:
    """None → backend auto-selection; a bool is passed through untouched."""
    return default_interpret() if interpret is None else bool(interpret)


def default_fold() -> str:
    """Mosaic compiles only the one-hot form; everything else runs the
    interpreter, where the scatter/sort segment folds are linear-time."""
    return "onehot" if backend_kind() == "tpu" else "segment"


def resolve_fold(fold: str | None) -> str:
    """None → backend auto-selection; an explicit strategy passes through."""
    if fold is None:
        return default_fold()
    if fold not in FOLDS:
        raise ValueError(f"unknown fold strategy {fold!r}; one of {FOLDS}")
    return fold


def tile_ok(block: int, full: int, align: int, *, interpret: bool) -> bool:
    """Whether ``block`` rows tile a ``full``-row axis of a compiled kernel:
    the whole axis, or a multiple of the hardware tile ``align`` (8 rows of
    a 2-D block, 128 lanes of a 1-D one).  The interpreter takes any
    block."""
    return interpret or block >= full or block % align == 0
