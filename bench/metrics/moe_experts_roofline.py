"""Roofline share of the held experts' grouped products: the least time
the chip could take for what they execute a round (the larger of their
FLOPs over the bf16 peak and their least bytes over the HBM bandwidth,
both from the held-copy counter, bench/costs/deepseek_v2.py) against
their measured device time a round (``moe_experts_ms``)."""
from bench import harness


def read(ctx):
    costs, peaks = ctx["costs"], ctx["peaks"]
    if not costs.get("experts_flops_per_round"):
        return None
    ms = harness.metric_reader("moe_experts_ms")(ctx)
    if not ms:
        return None
    least = max(costs["experts_flops_per_round"] / peaks["bf16_flops"],
                costs["experts_bytes_per_round"] / peaks["hbm_bytes_per_s"]
                ) / ctx["chips"]
    return 100.0 * least / (ms * 1e-3)
