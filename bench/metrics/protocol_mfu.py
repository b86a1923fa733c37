"""Roofline share of a whole protocol round: the least time the chip
could take for it (the larger of its least FLOPs over the bf16 peak and
its least bytes over the HBM bandwidth, bench/costs/protocol.py) over the
measured time per round."""


def read(ctx):
    costs, peaks = ctx["costs"], ctx["peaks"]
    if "round_bytes" not in costs or not ctx["counters"].get("rounds"):
        return None
    least = max(costs["round_flops"] / peaks["bf16_flops"],
                costs["round_bytes"] / peaks["hbm_bytes_per_s"])
    per_round = ctx["window"]["seconds"] / ctx["counters"]["rounds"]
    return 100.0 * least / per_round
