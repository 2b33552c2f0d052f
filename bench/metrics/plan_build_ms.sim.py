"""Host time in the program's plan_build spans per traced call."""
from lib.layers import plan_build_ms as read  # noqa: F401
