"""mfu.train: model operations per token (``bench.work``) times trained
tokens per second over the window, over the chips' published bf16 peak,
in percent.  Recomputation does not count."""


def read(r):
    if r["kind"] != "train":
        return None
    rate = r["tokens"] / r["window_s"]
    return 100.0 * r["flops_per_token"] * rate / (r["chips"] * r["peaks"]["flops_bf16"])
