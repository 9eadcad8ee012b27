"""Operations and bytes that a step needs, computed from the configuration's
sizes and the wire formats the cell declares.

These are the yardstick's counts, independent of how the program implements
a step: recomputation, masked blocks, padding and dequantised copies do not
count.  A configuration dict has the repository's ``ModelConfig`` field
names (``num_layers``, ``d_model``, ...), as ``bench/configs/*.json`` hold
them.

Conventions:

* a multiply-add is 2 operations; a matmul over a parameter matrix costs
  2 operations per parameter per token forward, 6 in training (forward,
  and the backward's two products);
* the depthwise convolution of the SSM is counted with the matmuls (its
  ``w x F`` weights are one multiply-add each per token);
* the embedding lookup is no matmul and is not counted; the output head is;
* attention at position ``i`` attends ``min(i + 1, window)`` keys: 4
  operations per key and query head dimension (scores and the weighted
  sum), 12 in training;
* the SSM is counted as its linear recurrence, 5 operations per state
  element and token (decay, input outer product and add: 3; the readout
  contraction: 2), 15 in training.
"""

from __future__ import annotations

#: wire format -> stored bytes per element (block-scaled formats carry one
#: scale byte per 32 elements)
FORMAT_BYTES = {
    "f32": 4.0, "bf16": 2.0, "t32": 4.0, "t16": 2.0, "t8": 1.0,
    "e4m3": 1.0, "e5m2": 1.0, "mxe4m3": 33 / 32, "mxe5m2": 33 / 32, "mxt8": 33 / 32,
}


def format_bytes(fmt: str) -> float:
    try:
        return FORMAT_BYTES[fmt]
    except KeyError:
        raise KeyError(f"bench/work.py has no byte count for format {fmt!r}") from None


def _has_attention(c) -> bool:
    return c["family"] != "ssm"


def _has_ssm(c) -> bool:
    return c["family"] in ("ssm", "hybrid")


def ssm_dims(c) -> dict:
    """Inner width, heads, state size, head dim, conv width and conv features."""
    d_in = c["ssm_expand"] * c["d_model"] if c["family"] == "ssm" else c["d_model"]
    hd = c["ssm_head_dim"]
    return {"d_in": d_in, "nh": d_in // hd, "N": c["ssm_state"], "hd": hd,
            "w": c["ssm_conv_width"], "F": d_in + 2 * c["ssm_state"]}


def layer_matmul_params(c) -> int:
    """Parameters of one layer that take part in a matmul-like product."""
    d = c["d_model"]
    n = 0
    if _has_attention(c):
        H, Kv, hd = c["num_heads"], c["num_kv_heads"], c["head_dim"]
        n += d * H * hd + 2 * d * Kv * hd + H * hd * d
        n += 3 * d * c["d_ff"]  # SwiGLU
    if _has_ssm(c):
        s = ssm_dims(c)
        n += d * (2 * s["d_in"] + 2 * s["N"] + s["nh"]) + s["d_in"] * d
        n += s["w"] * s["F"]  # depthwise conv
    return n


def head_params(c) -> int:
    return c["d_model"] * c["vocab_size"]


def matmul_params(c) -> int:
    return c["num_layers"] * layer_matmul_params(c) + head_params(c)


def mean_keys(seq: int, window: int) -> float:
    """Mean over positions 0..seq-1 of the keys a causal query attends,
    ``min(i + 1, window)`` (``window`` 0: no window)."""
    if window <= 0 or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def _keys(c, live: float) -> float:
    """Keys a decode query attends when ``live`` positions exist."""
    w = c.get("sliding_window", 0)
    return min(live, w) if w > 0 else live


def attn_flops_per_key(c) -> int:
    """Forward operations per (query, key) pair summed over heads."""
    return 4 * c["num_heads"] * c["head_dim"] if _has_attention(c) else 0


def ssm_state_elems(c) -> int:
    if not _has_ssm(c):
        return 0
    s = ssm_dims(c)
    return s["nh"] * s["N"] * s["hd"]


def train_flops_per_token(c, seq: int) -> float:
    """Model operations per trained token (forward and backward)."""
    L = c["num_layers"]
    f = 6.0 * matmul_params(c)
    f += 3.0 * L * attn_flops_per_key(c) * mean_keys(seq, c.get("sliding_window", 0))
    f += 15.0 * L * ssm_state_elems(c)
    return f


def decode_flops_per_step(c, batch: int, live: int) -> float:
    """Operations of one decode step of ``batch`` sequences whose newest
    token sits at position ``live - 1`` (so ``live`` positions exist)."""
    L = c["num_layers"]
    keys = _keys(c, live)
    per_seq = 2.0 * matmul_params(c)
    per_seq += L * attn_flops_per_key(c) * keys
    per_seq += 5.0 * L * ssm_state_elems(c)
    return batch * per_seq


def param_count(c) -> dict:
    """Parameters by where a decode step reads them: ``embed`` (the table),
    ``stacked`` (every per-layer leaf) and ``head``/``final`` (the output
    projection unless tied, and the final norm)."""
    d, L = c["d_model"], c["num_layers"]
    per = layer_matmul_params(c) - (ssm_dims(c)["w"] * ssm_dims(c)["F"] if _has_ssm(c) else 0)
    per += d  # ln1
    if _has_attention(c):
        per += d  # ln2
    if _has_ssm(c):
        s = ssm_dims(c)
        per += s["w"] * s["F"] + s["F"] + 3 * s["nh"] + s["d_in"]  # conv w, b; a, dt, D; norm
    return {"embed": c["vocab_size"] * d, "stacked": L * per,
            "head": 0 if c["tie_embeddings"] else head_params(c), "final": d}


def decode_bytes_per_step(c, wire: dict, batch: int, live: int,
                          conv_bytes: float, ssm_bytes: float) -> float:
    """Essential HBM bytes of one decode step.

    Weights in ``wire["weights"]`` read once (of the embedding table only the
    ``batch`` rows looked up, unless it is also the tied head); the K/V of the
    positions attention needs, ``min(live, window)`` per layer, in
    ``wire["kv_cache"]``, read, plus the new position written; SSM and conv
    state read and written at their stored bytes per element; f32 logits
    written.
    """
    L, d = c["num_layers"], c["d_model"]
    wb = format_bytes(wire["weights"])
    p = param_count(c)
    total = (p["stacked"] + p["head"]) * wb + p["final"] * 4.0
    total += (p["embed"] if c["tie_embeddings"] else batch * d) * wb
    if _has_attention(c):
        keys = _keys(c, live)
        per_pos = 2 * c["num_kv_heads"] * c["head_dim"] * format_bytes(wire["kv_cache"])
        total += L * batch * (keys + 1) * per_pos  # keys read, one written
    if _has_ssm(c):
        s = ssm_dims(c)
        total += 2 * L * batch * s["nh"] * s["N"] * s["hd"] * ssm_bytes
        total += 2 * L * batch * (s["w"] - 1) * s["F"] * conv_bytes
    total += batch * c["vocab_size"] * 4.0
    return total


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tf = flops / peaks["flops_bf16"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
