"""Device self time under the placement_walk named scope and outside
rank_sweep, over device busy time of the traced window."""
from lib.layers import walk_device_pct as read  # noqa: F401
