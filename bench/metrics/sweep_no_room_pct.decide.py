"""Share of the traced decisions' sweeps made because no shortlist node
had room and health for the demand (Placement.walk_counts[2])."""
from lib.layers import no_room_pct as read  # noqa: F401
