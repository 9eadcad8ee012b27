"""The ``ssm`` family (Mamba-2): a pre-normed Mamba-2 mixer of inner width
``ssm_expand x d_model`` per layer, no attention and no MLP."""

from __future__ import annotations

from bench.families import _blocks as b
from bench.work import Layer, Stack


def _d_in(c) -> int:
    return c["ssm_expand"] * c["d_model"]


def spec(c) -> dict:
    L = c["num_layers"]
    return {**b.embedding(c), "layers.ln1": b.norm(L, c["d_model"]), **b.mamba2(c, L, _d_in(c))}


def stack(c) -> Stack:
    layer = Layer(matmul=b.mamba2_matmul(c, _d_in(c)), **b.mamba2_counts(c, _d_in(c)))
    return Stack(layers=(layer,) * c["num_layers"])
