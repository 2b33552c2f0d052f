"""Share of the traced decisions' arrivals that the shortlist placed
without a sweep, of those placed or swept (Placement.walk_counts)."""
from lib.layers import hit_pct as read  # noqa: F401
