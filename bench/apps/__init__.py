"""Plain references of the applications behind the router, one module per
kind of application: ``bench/apps/<name>.py``, named by the configuration's
``application.reference`` (``dense`` where it names none).

Each module imports nothing of the program and exports:

* ``KEYS``: the application sizes a configuration states;
* ``check(app, cfg)``: raises ``ValueError`` unless the configuration's
  ``application`` entry is the program's registered model ``cfg``;
* ``forward_logits(app, params, tokens, dtype)``: logits (B, S, V) of a
  full causal forward over ``tokens`` (B, S), in float32 at the highest
  matmul precision, or in the lower ``dtype`` of the control
  (``bench/check.py::CONTROL``); it may run in blocks, so that a wide
  model fits;
* ``decode_flops(app, batch, cache_len)``: FLOPs of one decode step over
  ``batch`` slots.
"""

from __future__ import annotations

from pathlib import Path

from bench import registry


def for_application(app: dict, root: Path = registry.ROOT):
    """The reference module of a configuration's ``application`` entry."""
    return registry.load("apps", app.get("reference", "dense"), root)
