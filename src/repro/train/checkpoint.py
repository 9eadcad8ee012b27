"""Fault-tolerant checkpointing: atomic, async, optionally takum-compressed,
elastic-restore-capable.

Layout (one directory per step):

    ckpt_dir/
      step_000123/
        meta.json            # step, format, pytree structure, shapes, mesh
        arrays.npz           # flattened leaves (raw or takum-packed)
      LATEST                 # atomically-updated pointer file

Design notes for the 1000+-node deployment this models (DESIGN.md):
  * writes go to ``step_X.tmp`` then ``os.rename`` — a crashed writer never
    corrupts LATEST; file contents are fsync'd before the rename so the
    pointer never outruns the data;
  * every stored array carries a CRC32 in the meta (computed over the
    *stored* bytes, i.e. after wire packing) plus its stored dtype/shape —
    restore re-hashes and refuses corrupted bytes loudly
    (:class:`CheckpointCorruptionError`) instead of decoding garbage bit
    patterns into plausible-looking weights (DESIGN.md §8);
  * restore validates the schema and the wire format by name before
    touching any payload: an unregistered format, a missing meta key, or a
    leaf-count mismatch against the restore target raises
    :class:`CheckpointFormatError` naming expected vs found;
  * the writer runs on a background thread (training continues; ``wait()``
    joins before the next save or at shutdown);
  * wire compression (policy.checkpoint = 't16' / 'e4m3' / 'bf16' — any
    registered narrow wire format) halves/quarters checkpoint bytes.  Flat
    formats pack and unpack on the device with the jnp codecs
    (``lut.encode_jnp_fast``/``decode_jnp_fast``, bit-identical to the
    float64 numpy oracles on the f32 DAZ domain, NaN payloads aside).
    Through the oracles on the host, a 425M-parameter state took 450 s to
    save and 250 s to restore (TPU v5e host).  Block-scaled formats keep
    the oracle.  Decode on restore is the representable value (one
    quantisation on save, none after);
  * restore is sharding-agnostic: arrays come back as host numpy and are
    re-placed by the caller's current mesh (elastic restarts onto a
    different pod count).
"""

from __future__ import annotations

import functools
import json
import os
import shutil
import threading
import zlib
from typing import Any, Optional

import jax
import numpy as np

from repro.core import takum_np
from repro.core.formats import WIRE_FORMATS, wire_format
from repro.kernels.lut import decode_jnp_fast, encode_jnp_fast

#: meta.json schema: 2 adds per-leaf CRC32 + stored dtype/shape.  Schema-1
#: checkpoints (no "schema" key) restore without integrity verification.
SCHEMA_VERSION = 2


class CheckpointError(RuntimeError):
    """Base class for checkpoint integrity failures."""


class CheckpointCorruptionError(CheckpointError):
    """Stored bytes do not match their recorded CRC32 / are unreadable."""


class CheckpointFormatError(CheckpointError):
    """Schema or wire-format mismatch between checkpoint and this build."""


def _crc(a: np.ndarray) -> int:
    return zlib.crc32(np.ascontiguousarray(a).tobytes()) & 0xFFFFFFFF


@functools.cache
def _device_codec(fmt: str):
    """Jitted ``(encode, decode)`` of flat wire format ``fmt``: f32 -> packed
    bits and back, on the device that holds the array."""
    return (
        jax.jit(lambda x: encode_jnp_fast(x, fmt)),
        jax.jit(lambda b: decode_jnp_fast(b, fmt)),
    )


def _fsync_write(path: str, data: str) -> None:
    with open(path, "w") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


class CheckpointManager:
    def __init__(self, directory: str, *, fmt: str = "f32", keep: int = 3):
        self.dir = directory
        self.fmt = fmt
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------ save

    def save(self, step: int, tree: Any, *, blocking: bool = False) -> None:
        """Snapshot ``tree`` (pytree of arrays) at ``step``; async by default."""
        self.wait()  # one in-flight write at a time
        leaves, treedef = jax.tree.flatten(tree)
        wf = wire_format(self.fmt)
        compress = wf.name != "f32" and wf.nbits < 32
        dtypes = [np.dtype(x.dtype) for x in leaves]
        packed = [
            compress and not wf.is_block_scaled and np.issubdtype(dt, np.floating)
            for dt in dtypes
        ]
        encode = _device_codec(wf.name)[0] if any(packed) else None
        # device -> host copy, sync; flat-format leaves are packed first
        host = [np.asarray(encode(x) if p else x) for x, p in zip(leaves, packed)]

        def write():
            tmp = os.path.join(self.dir, f"step_{step:09d}.tmp")
            final = os.path.join(self.dir, f"step_{step:09d}")
            os.makedirs(tmp, exist_ok=True)
            arrays, meta_leaves = {}, []
            for i, a in enumerate(host):
                if packed[i]:
                    # the "takum" meta key stays for old-checkpoint compat
                    arrays[f"a{i}"] = a.astype(wf.np_storage)
                    meta_leaves.append({
                        "takum": wf.nbits if wf.family == "takum" else 0,
                        "wire": wf.name, "dtype": str(dtypes[i]),
                    })
                elif compress and np.issubdtype(a.dtype, np.floating):
                    # block-scaled: through the float64 numpy oracle.  The
                    # block codec moves whole 32-blocks on a flat view; the
                    # logical shape rides in the meta so restore can slice
                    # the padding back off
                    flat = a.astype(np.float64).reshape(-1)
                    pad = -len(flat) % 32
                    if pad:
                        flat = np.concatenate([flat, np.zeros(pad)])
                    bits = wf.encode_np(flat)
                    arrays[f"a{i}"] = bits.astype(wf.np_storage)
                    meta_leaves.append({
                        "takum": 0, "wire": wf.name,
                        "dtype": str(a.dtype), "shape": list(a.shape),
                    })
                else:
                    arrays[f"a{i}"] = a
                    meta_leaves.append({"takum": 0, "dtype": str(a.dtype)})
            for i in range(len(host)):
                # integrity record over the STORED bytes (post-packing):
                # restore verifies before any decode touches them
                a = arrays[f"a{i}"]
                meta_leaves[i]["crc"] = _crc(a)
                meta_leaves[i]["stored_dtype"] = str(a.dtype)
                meta_leaves[i]["stored_shape"] = list(a.shape)
            npz_path = os.path.join(tmp, "arrays.npz")
            np.savez(npz_path, **arrays)
            with open(npz_path, "rb+") as f:
                os.fsync(f.fileno())
            _fsync_write(
                os.path.join(tmp, "meta.json"),
                json.dumps({
                    "schema": SCHEMA_VERSION, "step": step, "fmt": self.fmt,
                    "num_leaves": len(host), "leaves": meta_leaves,
                }),
            )
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)
            _fsync_write(os.path.join(self.dir, "LATEST.tmp"), str(step))
            os.replace(os.path.join(self.dir, "LATEST.tmp"), os.path.join(self.dir, "LATEST"))
            self._gc()

        if blocking:
            write()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _gc(self) -> None:
        steps = sorted(self.all_steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:09d}"), ignore_errors=True)

    # --------------------------------------------------------------- restore

    def all_steps(self):
        return [
            int(d.split("_")[1])
            for d in os.listdir(self.dir)
            if d.startswith("step_") and not d.endswith(".tmp")
        ]

    def latest_step(self) -> Optional[int]:
        p = os.path.join(self.dir, "LATEST")
        if not os.path.exists(p):
            return None
        with open(p) as f:
            return int(f.read().strip())

    def restore(self, step: int, example_tree: Any) -> Any:
        """Restore into the structure of ``example_tree`` (host numpy leaves).

        The caller re-places leaves onto its current mesh — restoring onto a
        different topology than the one that saved is supported by design.

        Integrity (DESIGN.md §8): the meta schema, the named wire format and
        the leaf count are validated *before* any payload is decoded, and
        each stored array is re-hashed against its recorded CRC32.  Failures
        raise :class:`CheckpointFormatError` / :class:`CheckpointCorruptionError`
        with the expected-vs-found values — never a silent decode of garbage.
        """
        d = os.path.join(self.dir, f"step_{step:09d}")
        if not os.path.isdir(d):
            raise CheckpointCorruptionError(f"no checkpoint directory at {d}")
        try:
            with open(os.path.join(d, "meta.json")) as f:
                meta = json.load(f)
        except (OSError, json.JSONDecodeError) as e:
            raise CheckpointCorruptionError(
                f"unreadable meta.json in {d}: {e}"
            ) from e
        for key in ("step", "fmt", "num_leaves", "leaves"):
            if key not in meta:
                raise CheckpointFormatError(
                    f"meta.json in {d} is missing required key {key!r} "
                    f"(found keys: {sorted(meta)})"
                )
        schema = meta.get("schema", 1)
        if schema > SCHEMA_VERSION:
            raise CheckpointFormatError(
                f"checkpoint {d} uses meta schema {schema}; this build "
                f"supports <= {SCHEMA_VERSION}"
            )
        if meta["fmt"] not in WIRE_FORMATS:
            raise CheckpointFormatError(
                f"checkpoint {d} was saved in wire format {meta['fmt']!r}, "
                f"which this build does not register "
                f"(registered: {sorted(WIRE_FORMATS)})"
            )
        n_expect = jax.tree.flatten(example_tree)[1].num_leaves
        if meta["num_leaves"] != len(meta["leaves"]):
            raise CheckpointFormatError(
                f"meta.json in {d} is inconsistent: num_leaves="
                f"{meta['num_leaves']} but {len(meta['leaves'])} leaf records"
            )
        if meta["num_leaves"] != n_expect:
            raise CheckpointFormatError(
                f"checkpoint {d} holds {meta['num_leaves']} leaves but the "
                f"restore target expects {n_expect} — saved/restored trees "
                "do not match (wrong model config or policy?)"
            )
        try:
            z = np.load(os.path.join(d, "arrays.npz"))
        except Exception as e:  # OSError / zipfile.BadZipFile / ValueError
            raise CheckpointCorruptionError(
                f"unreadable arrays.npz in {d}: {e}"
            ) from e
        leaves = []
        for i, info in enumerate(meta["leaves"]):
            if f"a{i}" not in z.files:
                raise CheckpointCorruptionError(
                    f"arrays.npz in {d} is missing leaf a{i} "
                    f"(has {len(z.files)} arrays)"
                )
            try:
                # npz reads are lazy: zip-level decompression errors
                # (BadZipFile and friends) surface here, per member
                a = z[f"a{i}"]
            except Exception as e:
                raise CheckpointCorruptionError(
                    f"leaf a{i} in {d} is unreadable: {e}"
                ) from e
            if "crc" in info:
                got = _crc(a)
                if got != info["crc"]:
                    raise CheckpointCorruptionError(
                        f"leaf a{i} in {d} failed its integrity check: "
                        f"stored CRC32 {info['crc']:#010x}, recomputed "
                        f"{got:#010x} — bytes corrupted on disk"
                    )
            if info.get("wire"):
                if info["wire"] not in WIRE_FORMATS:
                    raise CheckpointFormatError(
                        f"leaf a{i} in {d} is packed as {info['wire']!r}, "
                        f"which this build does not register "
                        f"(registered: {sorted(WIRE_FORMATS)})"
                    )
                wf = wire_format(info["wire"])
                if wf.is_block_scaled:
                    shape = tuple(info["shape"])
                    vals = wf.decode_np(a.astype(np.uint8))
                    a = vals[: int(np.prod(shape))].reshape(shape).astype(info["dtype"])
                else:
                    decode = _device_codec(wf.name)[1]
                    a = np.asarray(decode(a.astype(wf.np_storage))).astype(info["dtype"])
            elif info["takum"]:
                # pre-registry checkpoints: bare takum width
                a = takum_np.decode(a.astype(np.uint64), info["takum"]).astype(info["dtype"])
            leaves.append(a)
        _, treedef = jax.tree.flatten(example_tree)
        return jax.tree.unflatten(treedef, leaves)
