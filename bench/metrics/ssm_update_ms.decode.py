"""ssm_update_ms.decode: device self time per decode step in the program's
``mamba.ssm_update`` scope, the one-token SSM state update and readout, in
ms (``bench.scopes``)."""

from bench.scopes import step_ms


def read(r):
    return step_ms(r, "decode", "mamba.ssm_update")
