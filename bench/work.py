"""Operations and bytes that a step needs, computed from the configuration's
sizes and the wire formats the cell declares.

These are the yardstick's counts, independent of how the program implements
a step: recomputation, masked blocks, padding and dequantised copies do not
count.  A configuration dict has the repository's ``ModelConfig`` field
names (``num_layers``, ``d_model``, ...), as ``bench/configs/*.json`` hold
them.  Its family (``bench/families/<family>.py``) says what each layer
computes, as a :class:`Layer`, and how many prefix positions precede every
sequence; the sums here are the same for every family.

Conventions:

* a multiply-add is 2 operations; a matmul over a parameter matrix costs
  2 operations per parameter per token forward, 6 in training (forward,
  and the backward's two products);
* the depthwise convolution of the SSM is counted with the matmuls (its
  ``w x F`` weights are one multiply-add each per token);
* the embedding lookup is no matmul and is not counted; the output head is;
* in layer ``l``, with window ``w_l`` (0: global) and a prefix of ``P``
  positions that every query attends whatever its window (meta tokens),
  a query at position ``i`` attends ``min(i + 1, w_l) + P`` keys
  (``i + 1 + P`` in a global layer): 4 operations per key and query head
  dimension (scores and the weighted sum), 12 in training;
* in training the prefix runs through the stack with every sequence: its
  ``P`` positions' matmul and SSM operations are counted with the
  sequence's (the head excepted), and prefix position ``j`` attends
  ``j + 1`` keys, all spread over the sequence's tokens;
* in decode the prefix is in the cache.  Each layer that attends reads the
  K/V of its keys, so K/V that layers share are counted once per reading
  layer (a layer's queries exist only once the layer before it has run);
  only the layer that stores K/V writes the new position;
* the SSM is counted as its linear recurrence, 5 operations per state
  element and token (decay, input outer product and add: 3; the readout
  contraction: 2), 15 in training.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from bench import families, weights

#: wire format -> stored bytes per element (block-scaled formats carry one
#: scale byte per 32 elements)
FORMAT_BYTES = {
    "f32": 4.0, "bf16": 2.0, "t32": 4.0, "t16": 2.0, "t8": 1.0,
    "e4m3": 1.0, "e5m2": 1.0, "mxe4m3": 33 / 32, "mxe5m2": 33 / 32, "mxt8": 33 / 32,
}


@dataclasses.dataclass(frozen=True)
class Layer:
    """What one decoder layer computes, per token and sequence."""

    matmul: int  # parameters that take part in a matmul-like product
    heads: int = 0  # query heads of its attention; 0: it does not attend
    head_dim: int = 0
    kv: int = 0  # K and V elements the cache holds per position
    window: int = 0  # keys a query reaches back; 0: global
    stores_kv: bool = True  # False: attends another layer's K/V, stores none
    ssm_state: int = 0  # SSM state elements per sequence
    conv_state: int = 0  # conv tail elements per sequence


@dataclasses.dataclass(frozen=True)
class Stack:
    layers: tuple[Layer, ...]
    prefix: int = 0  # positions every query attends, whatever its window


def format_bytes(fmt: str) -> float:
    try:
        return FORMAT_BYTES[fmt]
    except KeyError:
        raise KeyError(f"bench/work.py has no byte count for format {fmt!r}") from None


def stack(c) -> Stack:
    return families.load(c).stack(c)


def layer_matmul_params(c, i: int = 0) -> int:
    """Parameters of layer ``i`` that take part in a matmul-like product."""
    return stack(c).layers[i].matmul


def head_params(c) -> int:
    return c["d_model"] * c["vocab_size"]


def matmul_params(c) -> int:
    return sum(layer.matmul for layer in stack(c).layers) + head_params(c)


def mean_keys(seq: int, window: int) -> float:
    """Mean over positions 0..seq-1 of the keys a causal query attends,
    ``min(i + 1, window)`` (``window`` 0: no window)."""
    if window <= 0 or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def _keys(layer: Layer, live: float, prefix: int) -> float:
    """Keys a decode query of ``layer`` attends when ``live`` positions exist."""
    return (min(live, layer.window) if layer.window > 0 else live) + prefix


def _flops_per_key(layer: Layer) -> int:
    """Forward operations per (query, key) pair summed over heads."""
    return 4 * layer.heads * layer.head_dim


def train_flops_per_token(c, seq: int) -> float:
    """Model operations per trained token (forward and backward)."""
    s = stack(c)
    P = s.prefix
    rows = (seq + P) / seq  # positions through the stack per trained token
    f = 6.0 * (sum(layer.matmul for layer in s.layers) * rows + head_params(c))
    keys = lambda w: mean_keys(seq, w) + P + P * (P + 1) / (2 * seq)
    f += sum(3.0 * _flops_per_key(layer) * keys(layer.window) for layer in s.layers if layer.heads)
    f += 15.0 * sum(layer.ssm_state for layer in s.layers) * rows
    return f


def decode_flops_per_step(c, batch: int, live: int) -> float:
    """Operations of one decode step of ``batch`` sequences whose newest
    token sits at position ``live - 1`` (so ``live`` positions exist)."""
    s = stack(c)
    per_seq = 2.0 * matmul_params(c)
    per_seq += sum(_flops_per_key(layer) * _keys(layer, live, s.prefix)
                   for layer in s.layers if layer.heads)
    per_seq += 5.0 * sum(layer.ssm_state for layer in s.layers)
    return batch * per_seq


def param_count(c) -> dict:
    """Parameters by where a decode step reads them: ``embed`` (the table),
    ``stacked`` (every per-layer leaf) and ``head``/``final`` (the output
    projection unless tied, and the final norm).  Any other leaf (a
    prefix's embeddings) is read in prefill only."""
    out = {"embed": 0, "stacked": 0, "head": 0, "final": 0}
    group = {"embed": "embed", "lm_head": "head", "final_norm": "final"}
    for name, (shape, _, stacked) in weights.spec(c).items():
        key = "stacked" if stacked else group.get(name)
        if key:
            out[key] += int(np.prod(shape))
    return out


def decode_bytes_per_step(c, wire: dict, batch: int, live: int,
                          conv_bytes: float, ssm_bytes: float) -> float:
    """Essential HBM bytes of one decode step.

    Weights in ``wire["weights"]`` read once (of the embedding table only the
    ``batch`` rows looked up, unless it is also the tied head); in each layer
    that attends, the K/V of its keys in ``wire["kv_cache"]`` read, plus the
    new position written where the layer stores K/V; SSM and conv state
    read and written at their stored bytes per element; f32 logits written.
    """
    s = stack(c)
    wb = format_bytes(wire["weights"])
    p = param_count(c)
    total = (p["stacked"] + p["head"]) * wb + p["final"] * 4.0
    total += (p["embed"] if c["tie_embeddings"] else batch * c["d_model"]) * wb
    kvb = format_bytes(wire["kv_cache"])
    for layer in s.layers:
        if layer.heads:
            total += batch * (_keys(layer, live, s.prefix) + layer.stores_kv) * layer.kv * kvb
    total += 2 * batch * sum(layer.ssm_state for layer in s.layers) * ssm_bytes
    total += 2 * batch * sum(layer.conv_state for layer in s.layers) * conv_bytes
    total += batch * c["vocab_size"] * 4.0
    return total


def roofline_seconds(flops: float, nbytes: float, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tf = flops / peaks["flops_bf16"]
    tb = nbytes / peaks["hbm_bytes_per_s"]
    return (tf, "flops") if tf >= tb else (tb, "bytes")
