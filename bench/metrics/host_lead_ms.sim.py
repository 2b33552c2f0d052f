"""Mean host time from a call's plan_and_run span to its first device op."""
from lib.readers import host_lead_ms as read  # noqa: F401
