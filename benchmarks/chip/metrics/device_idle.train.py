"""Device idle share of the traced window in a training cell (moves
train_tok_s)."""
from trace_reduce import idle_percent as read  # noqa: F401
