"""Full rank sweeps per simulated lane-epoch (SimResult.rank_sweeps)."""


def read(ctx):
    c = ctx.counters
    return c["sweeps"] / c["lane_epochs"] if c.get("lane_epochs") else None
