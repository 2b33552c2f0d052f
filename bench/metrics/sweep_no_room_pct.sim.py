"""Share of the traced calls' sweeps, over all lanes, made because no
shortlist node had room and health (SimResult.walk_counts[2])."""
from lib.layers import no_room_pct as read  # noqa: F401
