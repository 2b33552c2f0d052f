"""Rank-sweep kernel time against its least HBM time (bytes / peak)."""
from lib.readers import sweep_roofline_pct as read  # noqa: F401
