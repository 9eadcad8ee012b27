"""The ``hybrid`` family (Hymba as the repository's program defines it): a
sliding-window GQA attention branch and a Mamba-2 branch of inner width
``d_model`` read the same normed input in parallel, then a SwiGLU MLP.
Every layer has the same window and stores its own K/V; there is no
prefix (``bench/ref/hybrid.py`` lists the departures from the paper)."""

from __future__ import annotations

from bench.families import _blocks as b
from bench.work import Layer, Stack


def spec(c) -> dict:
    L, d = c["num_layers"], c["d_model"]
    return {**b.embedding(c), "layers.ln1": b.norm(L, d), "layers.ln2": b.norm(L, d),
            **b.attention(c, L), **b.swiglu(c, L), **b.mamba2(c, L, d)}


def stack(c) -> Stack:
    d = c["d_model"]
    layer = Layer(matmul=b.attention_matmul(c) + b.swiglu_matmul(c) + b.mamba2_matmul(c, d),
                  window=c.get("sliding_window", 0), **b.attention_counts(c),
                  **b.mamba2_counts(c, d))
    return Stack(layers=(layer,) * c["num_layers"])
