"""The one traffic generator.  A mix is a data file ``bench/traffic/<name>.json``
of parameters; this module turns it and a run's seed into the inputs.

Kinds:

* ``train``: ``batch`` x ``seq`` token ids, uniform over the vocabulary,
  a fresh batch for every step (``fold_in(data key, step)``), so rows never
  repeat.  ``mesh`` is the program's mesh spec.
* ``decode``: ``batch`` sessions, each a ``prompt``-token prompt (uniform
  ids, ``fold_in(data key, session)``), prefilled ``prefill_rows`` sessions
  at a time into a cache of ``cache_len`` positions, then decoded greedily
  for the whole window.  Where ``replay_at`` is set, a session whose
  position reaches it is replayed from the end of its prompt (the cache's
  prompt slots and the SSM state at the prompt's end are kept), so the
  sessions' context stays between ``prompt`` and ``replay_at``.
  ``check_sessions`` sessions, drawn from the seed, are compared with the
  reference after the window.
"""

from __future__ import annotations

import json
import pathlib

import jax
import jax.numpy as jnp

from bench.weights import seed_key

DIR = pathlib.Path(__file__).resolve().parent / "traffic"
KINDS = {
    "train": {"batch", "seq", "mesh", "check_steps", "ref_rows"},
    "decode": {"batch", "prompt", "cache_len", "prefill_rows", "replay_at",
               "check_sessions", "mesh"},
}


def load(name: str) -> dict:
    spec = json.loads((DIR / f"{name}.json").read_text())
    kind = spec.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic {name}: unknown kind {kind!r}")
    missing = KINDS[kind] - set(spec)
    if missing:
        raise ValueError(f"traffic {name}: missing {sorted(missing)}")
    return spec


def train_batch_fn(spec: dict, vocab: int, seed: int):
    """``batch(step) -> {"tokens": int32 [batch, seq]}``, made on the device."""
    key = seed_key(seed, "data")
    shape = (spec["batch"], spec["seq"])
    make = jax.jit(lambda k, s: jax.random.randint(jax.random.fold_in(k, s), shape, 0, vocab))
    return lambda step: {"tokens": make(key, jnp.int32(step))}


def prompts(spec: dict, vocab: int, seed: int):
    """int32 [batch, prompt] prompts, made on the device in one call."""
    n, p = spec["batch"], spec["prompt"]
    return jax.jit(lambda k: jax.vmap(
        lambda i: jax.random.randint(jax.random.fold_in(k, i), (p,), 0, vocab)
    )(jnp.arange(n)))(seed_key(seed, "data"))


def check_sessions(spec: dict, seed: int) -> list[int]:
    """The sessions compared with the reference, drawn from the seed."""
    k = jax.random.permutation(seed_key(seed, "check"), spec["batch"])
    return sorted(int(i) for i in k[: spec["check_sessions"]])
