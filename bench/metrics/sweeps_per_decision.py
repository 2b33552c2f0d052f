"""Full rank sweeps per decision over the window (Placement.n_sweeps)."""


def read(ctx):
    c = ctx.counters
    return c["sweeps"] / c["calls"] if c.get("calls") else None
