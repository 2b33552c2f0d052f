"""Device idle share of the traced window: 1 - busy union / window."""
from lib.readers import idle_pct as read  # noqa: F401
