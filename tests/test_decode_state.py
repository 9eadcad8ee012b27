"""Where the decode state lives in the serving cache.

The cache keeps L on axis 0 and B on axis 1 of the stacked conv tail and
SSM state, which stacking caches along the batch and ``dist.sharding``
rely on, and the SSM state's last two axes follow ``mamba2.state_axes``:
the larger of N and hd minor, a tie kept as [.., N, hd].  That the serve
step updates this stack in place is checked by a compile for the chip
(``tests/test_chip_compile.py``).
"""

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.models import transformer as T
from repro.models.mamba2 import state_axes

# smoke configs by name: mamba2's has N == hd, hymba's N < hd, and the
# third N > hd, the order that stores the state [.., hd, N]
CFGS = {
    "mamba2_780m": lambda: configs.get_smoke("mamba2_780m"),
    "hymba_1_5b": lambda: configs.get_smoke("hymba_1_5b"),
    "mamba2_n_gt_hd": lambda: configs.get_smoke("mamba2_780m").with_(
        ssm_state=32, ssm_head_dim=16),
}


@pytest.mark.parametrize("arch", sorted(CFGS))
def test_cache_keeps_layers_then_batch(arch):
    cfg = CFGS[arch]()
    L, B, S = cfg.num_layers, 3, 16
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    tokens = jax.ShapeDtypeStruct((B, 8), jnp.int32)
    _, filled = jax.eval_shape(lambda p, t: T.prefill(cfg, p, t, cache_len=S), params, tokens)
    zero = jax.eval_shape(lambda: T.init_cache(cfg, B, S))
    N, hd = cfg.ssm_state, cfg.ssm_head_dim
    nh = T._ssm_d_in(cfg) // hd
    last = (hd, N) if N > hd else (N, hd)
    for cache in (filled, zero):
        assert cache.ssm.shape == (L, B, nh) + last
        assert cache.ssm.dtype == jnp.float32
        assert cache.conv.shape[:2] == (L, B)
    assert filled.conv.shape == zero.conv.shape


def test_state_axes_put_the_larger_axis_minor():
    assert state_axes(128, 64) == "dn"  # mamba2_780m: N on the lanes
    assert state_axes(16, 64) == "nd"  # hymba_1_5b
    assert state_axes(64, 64) == "nd"  # a tie keeps [.., N, hd]
