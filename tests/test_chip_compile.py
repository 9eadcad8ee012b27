"""The main-path Pallas kernels compile for a TPU v5e at hymba_1_5b widths,
and the serve step updates the stacked SSM state in place there.

Each test lowers one kernel natively (``interpret=False``) and compiles it
with the TPU compiler for one chip of a described ``v5e:2x2`` topology — no
chip is attached, nothing runs.  This is what refuses a kernel that interpret
mode accepts: an op Mosaic cannot legalize, a gather it cannot lower, a block
not aligned to the tiling, too much VMEM.  Every compiled program must carry
the kernel as a ``tpu_custom_call``.

The topology is described inside a module-scoped fixture (never at import):
only one process may load the TPU compiler library, and pytest-xdist workers
all import this file.  The persistent compilation cache is off around the
compiles, since an entry compiled for a described chip cannot be read back.
"""

import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core.formats import wire_format
from repro.dist import step as dstep
from repro.kernels.takum_attention import takum_decode_attention
from repro.kernels.takum_codec import takum_decode_2d, takum_encode_2d
from repro.kernels.takum_matmul import takum_matmul
from repro.launch.mesh import parse_mesh
from repro.models import transformer as T
from repro.quant import blockscale
from repro.quant.policy import POLICIES

CFG = configs.get("hymba_1_5b")
BATCH, KV_LEN = 8, 4096
FORMATS = ("t8", "t16", "e4m3", "mxt8")
OPS = ("encode", "decode", "matmul", "decode_attention")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test against
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _program(op, fmt, shape):
    """(function, argument shapes) of one native kernel call."""
    wf = wire_format(fmt)
    K, N = CFG.d_model, CFG.d_ff
    H, Hkv, hd = CFG.num_heads, CFG.num_kv_heads, CFG.resolved_head_dim
    cols = blockscale.payload_len(N) if wf.is_block_scaled else N
    kv_d = blockscale.payload_len(hd) if wf.is_block_scaled else hd
    w_bits = shape((K, cols), wf.storage)
    kv = shape((BATCH, Hkv, KV_LEN, kv_d), wf.storage)
    if op == "encode":
        return lambda x: takum_encode_2d(x, fmt, interpret=False), (shape((K, N), jnp.float32),)
    if op == "decode":
        return lambda b: takum_decode_2d(b, fmt, interpret=False), (w_bits,)
    if op == "matmul":
        return (lambda x, w: takum_matmul(x, w, fmt, interpret=False),
                (shape((BATCH, K), jnp.float32), w_bits))
    return (lambda q, k, v: takum_decode_attention(q, k, v, fmt, interpret=False),
            (shape((BATCH, H, hd), jnp.float32), kv, kv))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("op", OPS)
def test_kernel_compiles_for_v5e(op, fmt, one_chip, no_persistent_cache):
    shape = lambda s, dt: jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
    fn, args = _program(op, fmt, shape)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["mamba2_780m", "hymba_1_5b"])
def test_decode_step_updates_the_ssm_state_in_place(arch, one_chip, no_persistent_cache):
    """The serve step, cache donated, at published widths cut to 2 layers
    and a 256-token vocabulary.  The stacked conv and SSM state ride in the
    decode layer scan's carry, so the compiled step aliases them with its
    output and copies neither the stack nor a layer's slice of it.  Passed
    through the scan as xs -> ys they come back as a second whole-state
    buffer, copied into the donated output, and (where the update computes
    in another layout, as at mamba2_780m's N=128, hd=64) each layer's slice
    is relaid on the way in and out.  The CPU backend copies the carried
    stack whatever the threading, so only this compile can tell them apart."""
    cfg = configs.get(arch).with_(num_layers=2, vocab_size=256, quant=POLICIES["takum"])
    B, S = 64, 16
    put = lambda t: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip), t)
    cache = put(jax.eval_shape(lambda: T.init_cache(cfg, B, S)))
    step = jax.jit(dstep.make_serve_step(cfg, parse_mesh("1x1")), donate_argnums=(2,))
    compiled = step.lower(
        put(dstep.serve_param_shapes(cfg)),
        {"token": jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)}, cache,
    ).compile()
    st = cache.ssm.shape
    state_shapes = {",".join(map(str, s)) for s in (st, (1,) + st[1:], st[1:])}
    copied = [s for s in re.findall(r"= f32\[([\d,]+)\]\S* copy\(", compiled.as_text())
              if s in state_shapes]
    assert not copied, copied
    ma = compiled.memory_analysis()
    nbytes = lambda a: a.size * a.dtype.itemsize
    assert ma.alias_size_in_bytes >= nbytes(cache.ssm) + nbytes(cache.conv), ma
    if cfg.family == "ssm":  # the hybrid's temporaries are its attention's and weights'
        assert ma.temp_size_in_bytes < nbytes(cache.ssm), ma
