"""Parameter specs and counts of the blocks the families share: the
embedding and head, norms, GQA attention, the SwiGLU MLP and the Mamba-2
mixer, as the repository's models define them.  The distributions are the
repository's conventions: normal matrices scaled by ``fan_in ** -0.5``,
zero norm gains, Mamba-2's ``A``, ``dt`` bias and ``D``.  ``n`` is the
length of a stack; ``at`` the prefix of the names.
"""

from __future__ import annotations


def embedding(c) -> dict:
    """The embedding table, the final norm and, unless tied, the output head."""
    d, V = c["d_model"], c["vocab_size"]
    s = {"embed": ((V, d), ("normal", d ** -0.5), False),
         "final_norm": ((d,), ("zeros",), False)}
    if not c["tie_embeddings"]:
        s["lm_head"] = ((d, V), ("normal", d ** -0.5), False)
    return s


def norm(n: int, d: int) -> tuple:
    return ((n, d), ("zeros",), True)


def attention(c, n: int, at: str = "layers.attn.") -> dict:
    d, H, Kv, hd = c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"]
    return {at + "wq": ((n, d, H * hd), ("normal", d ** -0.5), True),
            at + "wk": ((n, d, Kv * hd), ("normal", d ** -0.5), True),
            at + "wv": ((n, d, Kv * hd), ("normal", d ** -0.5), True),
            at + "wo": ((n, H * hd, d), ("normal", (H * hd) ** -0.5), True)}


def attention_matmul(c) -> int:
    d, H, Kv, hd = c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"]
    return d * H * hd + 2 * d * Kv * hd + H * hd * d


def attention_counts(c) -> dict:
    """``bench.work.Layer`` fields of a GQA attention branch."""
    return {"heads": c["num_heads"], "head_dim": c["head_dim"],
            "kv": 2 * c["num_kv_heads"] * c["head_dim"]}


def swiglu(c, n: int, at: str = "layers.mlp.") -> dict:
    d, f = c["d_model"], c["d_ff"]
    return {at + "wi": ((n, d, f), ("normal", d ** -0.5), True),
            at + "wg": ((n, d, f), ("normal", d ** -0.5), True),
            at + "wo": ((n, f, d), ("normal", f ** -0.5), True)}


def swiglu_matmul(c) -> int:
    return 3 * c["d_model"] * c["d_ff"]


def mamba2_dims(c, d_in: int) -> dict:
    """Inner width, heads, state size, head dim, conv width and conv features."""
    hd = c["ssm_head_dim"]
    return {"d_in": d_in, "nh": d_in // hd, "N": c["ssm_state"], "hd": hd,
            "w": c["ssm_conv_width"], "F": d_in + 2 * c["ssm_state"]}


def mamba2(c, n: int, d_in: int, at: str = "layers.ssm.") -> dict:
    d = c["d_model"]
    m = mamba2_dims(c, d_in)
    nh, N, w, F = m["nh"], m["N"], m["w"], m["F"]
    return {at + "in_proj": ((n, d, 2 * d_in + 2 * N + nh), ("normal", d ** -0.5), True),
            at + "conv_w": ((n, w, F), ("normal", 0.2), True),
            at + "conv_b": ((n, F), ("zeros",), True),
            at + "a_log": ((n, nh), ("a_log",), True),
            at + "dt_bias": ((n, nh), ("full", -4.6), True),
            at + "D": ((n, nh), ("ones",), True),
            at + "norm_g": ((n, d_in), ("zeros",), True),
            at + "out_proj": ((n, d_in, d), ("normal", d_in ** -0.5), True)}


def mamba2_matmul(c, d_in: int) -> int:
    """In and out projections, and the depthwise conv (one multiply-add per
    weight and token)."""
    m = mamba2_dims(c, d_in)
    return c["d_model"] * (2 * d_in + 2 * m["N"] + m["nh"]) + d_in * c["d_model"] + m["w"] * m["F"]


def mamba2_counts(c, d_in: int) -> dict:
    """``bench.work.Layer`` fields of a Mamba-2 mixer."""
    m = mamba2_dims(c, d_in)
    return {"ssm_state": m["nh"] * m["N"] * m["hd"], "conv_state": (m["w"] - 1) * m["F"]}
