"""Model families, one module each: ``bench/families/<family>.py``, found by
a configuration's ``family`` as ``bench/ref/<family>.py`` holds that
family's reference.

A family module defines:

* ``spec(c) -> {name: (shape, init, stacked)}``: every parameter of
  configuration ``c``, named as the program's parameter tree nests it
  (``layers.attn.wq``).  ``init`` is how ``bench.weights`` draws it;
  ``stacked`` says its leading axis is a stack of per-layer slices, of
  whatever length (a stack of the layers that store K/V need not be
  ``num_layers`` long).  The embedding is ``embed``, the output head
  ``lm_head`` (absent when tied) and the final norm ``final_norm``.
* ``stack(c) -> bench.work.Stack``: what each layer computes, as
  ``bench.work`` counts it, and the prefix of positions every query
  attends.

Shared blocks (embedding, attention, SwiGLU, the Mamba-2 mixer) are in
``_blocks``, which is no family.
"""

from __future__ import annotations

import importlib


def load(c):
    """The family module of configuration ``c``."""
    return importlib.import_module(f"bench.families.{c['family']}")
