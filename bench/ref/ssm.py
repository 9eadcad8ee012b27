"""One decoder layer of the ``ssm`` family (Mamba-2): a pre-normed Mamba-2
mixer on the residual stream, no attention and no MLP."""

from __future__ import annotations

from bench.ref.common import rms_norm, ssd_mixer


def layer(c, w, x, num):
    h = rms_norm(x, w["ln1"], c["norm_eps"])
    return x + ssd_mixer(c, {n[4:]: a for n, a in w.items() if n.startswith("ssm.")}, h, num)
