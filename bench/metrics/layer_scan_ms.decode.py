"""layer_scan_ms.decode: device self time per decode step in the program's
``layers`` scope, the layer scan's own work (slicing, the in-place write of
each layer's state slice), in ms (``bench.scopes``)."""

from bench.scopes import step_ms


def read(r):
    return step_ms(r, "decode", "layers")
