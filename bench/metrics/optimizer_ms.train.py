"""optimizer_ms.train: device self time per train step in the program's
``optimizer`` scope, the AdamW update (moment decode, update, t16 re-
encode), in ms (``bench.scopes``)."""

from bench.scopes import step_ms


def read(r):
    return step_ms(r, "train", "optimizer")
