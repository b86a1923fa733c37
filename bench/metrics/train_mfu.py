"""Model FLOP/s utilisation of training: the forward and backward FLOPs
each token requires (3 x forward, recomputation not counted) times the
tokens trained per second of the window, over the chips' bf16 peak."""


def read(ctx):
    per_token = ctx["costs"].get("train_flops_per_token")
    if per_token is None or not ctx["counters"].get("rounds"):
        return None
    c, w = ctx["counters"], ctx["window"]
    tokens_per_s = c["tokens_per_round"] * c["rounds"] / w["seconds"]
    return 100.0 * per_token * tokens_per_s / (
        ctx["chips"] * ctx["peaks"]["bf16_flops"])
