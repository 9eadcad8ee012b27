"""idle_share.decode: the share of the traced window in which no operation
runs on the device (averaged over the cell's chips), in percent."""


def read(r):
    if r["kind"] != "decode" or not r["trace"]:
        return None
    return 100.0 * r["trace"]["idle_share"]
