"""Load of the MoE share's busiest held expert: per window round, the
worst MoE layer's largest held-expert copy count over the held experts'
mean (1 is an even load), averaged over the rounds, from the program's
``moe_load_ratio`` counter in the Trainer's round record. The held
experts' products run in row chunks over the copies sorted by expert, so
their time follows the held copies; this says how evenly the router
spreads them."""


def read(ctx):
    return ctx["counters"].get("moe_load_ratio")
