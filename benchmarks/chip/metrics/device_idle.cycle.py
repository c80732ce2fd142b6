"""Device idle share of the traced window in a cycle cell (moves
preempt_durable_s)."""
from trace_reduce import idle_percent as read  # noqa: F401
