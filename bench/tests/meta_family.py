"""A model family built at test time, to show that the harness takes one
from new files alone: a hybrid layer (GQA attention beside a Mamba-2 mixer
of inner width ``ssm_expand x d_model``, then a SwiGLU MLP) with a window
per layer (0: global), layers that attend another layer's K/V and store
none, and a prefix of ``meta_tokens`` learned positions that every query
attends whatever its window.  K and V projections are stacked over the
layers that store K/V, not over ``num_layers``.

This one module plays three parts, each registered for the length of a
test under the name the harness looks it up by (``FAMILY``):

* the family (``bench/families/<family>.py``): ``spec`` and ``stack``;
* its reference (``bench/ref/<family>.py``): ``layers``, which owns the
  layer loop;
* a stand-in for the program (``Program``): the model functions the
  program's steps call (``repro.dist.step.T``) and a ``ModelConfig`` with
  the family's sizes, so the program's own train step, AdamW, weight
  packing and serve steps run it.  Its forward is the reference's,
  optionally with the K/V sharing or the prefix left out: the faults the
  comparison with the reference has to catch.
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import types
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from bench import program
from bench.families import _blocks as b
from bench.ref.common import F32, HI, mm, rms_norm, rope, ssd_mixer
from bench.work import Layer, Stack

ROOT = pathlib.Path(__file__).resolve().parents[2]
FAMILY = "meta_hybrid"


def config(**sizes) -> dict:
    """A configuration file's contents, JSON lists and all."""
    cfg = json.loads((ROOT / "bench" / "configs" / "mamba2_780m.json").read_text())
    cfg.update(name="tiny_meta", family=FAMILY, num_layers=4, d_model=32, num_heads=4,
               num_kv_heads=2, head_dim=8, d_ff=64, vocab_size=128, ssm_state=8,
               ssm_head_dim=8, ssm_expand=2, ssm_conv_width=4, rope_theta=10000.0,
               tie_embeddings=False, layer_windows=[0, 8, 8, 16], kv_from=[0, 0, 2, 3],
               meta_tokens=4)
    cfg.update(sizes)
    return cfg


# ---------------------------------------------------------------------------
# the family: parameters and counts
# ---------------------------------------------------------------------------


def _stores(c) -> list[int]:
    """The layers that store K/V, in the order of the K/V stack."""
    return [i for i, src in enumerate(c["kv_from"]) if src == i]


def spec(c) -> dict:
    L, d, P = c["num_layers"], c["d_model"], c["meta_tokens"]
    att = b.attention(c, L)
    kv = b.attention(c, len(_stores(c)), at="kv.")
    return {**b.embedding(c), "meta": ((P, d), ("normal", 1.0), False),
            "layers.ln1": b.norm(L, d), "layers.ln2": b.norm(L, d),
            "layers.attn.wq": att["layers.attn.wq"], "layers.attn.wo": att["layers.attn.wo"],
            "kv.wk": kv["kv.wk"], "kv.wv": kv["kv.wv"],
            **b.swiglu(c, L), **b.mamba2(c, L, c["ssm_expand"] * d)}


def stack(c) -> Stack:
    d, d_in = c["d_model"], c["ssm_expand"] * c["d_model"]
    kv_matmul = 2 * d * c["num_kv_heads"] * c["head_dim"]
    layers = []
    for i, (w, src) in enumerate(zip(c["layer_windows"], c["kv_from"], strict=True)):
        mm_ = (b.attention_matmul(c) - (0 if src == i else kv_matmul) + b.swiglu_matmul(c)
               + b.mamba2_matmul(c, d_in))
        layers.append(Layer(matmul=mm_, window=w, stores_kv=src == i, **b.attention_counts(c),
                            **b.mamba2_counts(c, d_in)))
    return Stack(layers=tuple(layers), prefix=c["meta_tokens"])


# ---------------------------------------------------------------------------
# the reference
# ---------------------------------------------------------------------------


def _attend(q, k, v, window: int, prefix: int):
    """q [B, T, H, hd], k/v [B, T, Kv, hd] over positions that begin with
    the prefix: causal, within ``window`` (0: all) or in the prefix."""
    B, T, H, hd = q.shape
    Kv = k.shape[2]
    qp, kp = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    ok = kp <= qp
    if window:
        ok &= (qp - kp < window) | (kp < prefix)
    qg = q.reshape(B, T, Kv, H // Kv, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k, precision=HI) * hd ** -0.5
    p = jax.nn.softmax(jnp.where(ok, s, -jnp.inf), axis=-1)
    return jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HI).reshape(B, T, H * hd)


def layers(c, params, x, num, share: bool = True, prefix: bool = True):
    """x [B, S, d] -> [B, S, d] through every layer, the prefix in front.
    ``share`` False: a reading layer computes K/V from its own input with
    the storing layer's weights; ``prefix`` False: no prefix."""
    H, Kv, hd, eps = c["num_heads"], c["num_kv_heads"], c["head_dim"], c["norm_eps"]
    P = c["meta_tokens"] if prefix else 0
    B, S, _ = x.shape
    if P:
        x = jnp.concatenate([jnp.broadcast_to(params["meta"], (B,) + params["meta"].shape), x], 1)
    T = P + S
    pos = jnp.arange(T)
    at = {l: j for j, l in enumerate(_stores(c))}
    kv = {}
    for i, (w, src) in enumerate(zip(c["layer_windows"], c["kv_from"], strict=True)):
        lw = {n[len("layers."):]: a[i] for n, a in params.items() if n.startswith("layers.")}
        h = rms_norm(x, lw["ln1"], eps)
        q = rope(mm(num, h, lw["attn.wq"]).reshape(B, T, H, hd), pos, c["rope_theta"])
        if src == i or not share:
            j = at[src]
            k = rope(mm(num, h, params["kv.wk"][j]).reshape(B, T, Kv, hd), pos, c["rope_theta"])
            v = mm(num, h, params["kv.wv"][j]).reshape(B, T, Kv, hd)
            kv[i] = (num.kv(k), num.kv(v))
        k, v = kv[src] if share else kv[i]
        att = mm(num, _attend(q, k, v, w, P), lw["attn.wo"])
        ssm = ssd_mixer(c, {n[4:]: a for n, a in lw.items() if n.startswith("ssm.")}, h, num)
        x = x + 0.5 * (att + ssm)
        h2 = rms_norm(x, lw["ln2"], eps)
        x = x + mm(num, jax.nn.silu(mm(num, h2, lw["mlp.wg"])) * mm(num, h2, lw["mlp.wi"]),
                   lw["mlp.wo"])
    return x[:, P:]


# ---------------------------------------------------------------------------
# a stand-in for the program
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MetaConfig(program.ModelConfig):
    layer_windows: tuple = ()
    kv_from: tuple = ()
    meta_tokens: int = 0


class Cache(NamedTuple):
    """The tokens so far (batch on axis 1, as the harness stacks caches);
    ``conv``/``ssm`` only as the harness's replay carries them."""

    tokens: Any  # int32 [1, B, cache_len]
    pos: Any
    conv: Any
    ssm: Any


class Program:
    """``repro.dist.step.T`` for the family: each step runs the reference
    over every position so far, in float32, with ``faults`` (keyword
    arguments of :func:`layers`) applied."""

    def __init__(self, **faults):
        self.faults = faults

    @staticmethod
    def _c(cfg) -> dict:
        return {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg) if f.name != "quant"}

    def init_params(self, cfg, key, dtype=jnp.float32):
        tree: dict = {}
        for name, (shape, _, _) in spec(self._c(cfg)).items():
            *parents, leaf = name.split(".")
            node = tree
            for p in parents:
                node = node.setdefault(p, {})
            node[leaf] = jnp.zeros(shape, dtype)
        return tree

    def forward(self, cfg, params, tokens):
        c, p = self._c(cfg), program.from_tree(params)
        x = layers(c, p, p["embed"][tokens], F32, **self.faults)
        return mm(F32, rms_norm(x, p["final_norm"], c["norm_eps"]), p["lm_head"])

    def loss_fn(self, cfg, params, batch, *, aux_weight: float = 0.01):
        tokens = batch["tokens"]
        logits = self.forward(cfg, params, tokens)[:, :-1]
        gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
        ce = jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)
        return ce, {"ce": ce, "aux": jnp.float32(0)}

    def prefill(self, cfg, params, tokens, media=None, *, cache_len=None):
        B, S = tokens.shape
        buf = jnp.zeros((1, B, cache_len or S), jnp.int32).at[0, :, :S].set(tokens)
        logits = self.forward(cfg, params, buf[0])[:, S - 1]
        state = jnp.zeros((cfg.num_layers, B, 1), jnp.float32)
        return logits, Cache(tokens=buf, pos=jnp.int32(S), conv=state, ssm=state)

    def decode_step(self, cfg, params, token, cache, media=None):
        buf = lax.dynamic_update_slice(cache.tokens, token[None, :, None].astype(jnp.int32),
                                       (0, 0, cache.pos))
        logits = lax.dynamic_index_in_dim(self.forward(cfg, params, buf[0]), cache.pos, axis=1,
                                          keepdims=False)
        return logits, cache._replace(tokens=buf, pos=cache.pos + 1)


def install(monkeypatch, **faults) -> None:
    """For the length of a test: this module as the family and its
    reference, and the stand-in program (with ``faults``) in the
    program's place."""
    mod = sys.modules[__name__]
    monkeypatch.setitem(sys.modules, f"bench.families.{FAMILY}", mod)
    monkeypatch.setitem(sys.modules, f"bench.ref.{FAMILY}", mod)
    monkeypatch.setattr(program, "ModelConfig", MetaConfig)
    prog = Program(**faults)
    monkeypatch.setattr(program.dstep, "T", types.SimpleNamespace(
        init_params=prog.init_params, loss_fn=prog.loss_fn, prefill=prog.prefill,
        decode_step=prog.decode_step))
