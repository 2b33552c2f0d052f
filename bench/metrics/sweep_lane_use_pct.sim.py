"""Share of the batched sweep launches' lane slots that a stalled lane
used: lane sweeps over (rounds x lanes) of each ensemble bucket
(SimResult.rank_sweeps, SimResult.sweep_rounds)."""
from lib.layers import lane_use_pct as read  # noqa: F401
