"""``idle_share.train``: 1 - (union of device-op intervals / traced
window), in %, from the profiler trace of the training window."""


def read(ctx):
    t = ctx.get("trace")
    if not t or ctx.get("train_window") is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
