"""weights_ms.decode: device self time per decode step in the program's
``serve.weights`` scope, the serve step's expansion of the stored weights
(``dequantize_params``), in ms (``bench.scopes``)."""

from bench.scopes import step_ms


def read(r):
    return step_ms(r, "decode", "serve.weights")
