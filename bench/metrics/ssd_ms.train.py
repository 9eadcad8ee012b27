"""ssd_ms.train: device self time per train step in the program's ``mamba.ssd``
scope, the Mamba-2 SSD scan (chunked state passing), forward, backward and
recompute, in ms (``bench.scopes``)."""

from bench.scopes import step_ms


def read(r):
    return step_ms(r, "train", "mamba.ssd")
