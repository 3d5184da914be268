"""``mfu.train``: the share of the chips' bf16 peak that the LSTM local
step's needed FLOPs make at the window's rounds per second.  FLOPs are
counted for the expected active nodes only (``bench/flops.py``)."""
from bench.flops import federation_round_flops


def read(ctx):
    w = ctx.get("train_window")
    if not w or w["seconds"] <= 0:
        return None
    flops = federation_round_flops(ctx["config"], ctx["traffic"]["inactive_ratio"])
    rate = flops * w["rounds"] / w["seconds"]
    return 100.0 * rate / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
