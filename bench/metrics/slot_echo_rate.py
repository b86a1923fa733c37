"""Share of TDMA slots in which the worker sent an echo instead of its
raw gradient, from the protocol trace's per-round ``n_echo``."""


def read(ctx):
    c = ctx["counters"]
    if not c.get("slots") or "n_echo" not in c:
        return None
    return 100.0 * c["n_echo"] / c["slots"]
