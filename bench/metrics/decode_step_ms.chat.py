"""Device milliseconds per decode step in the chat cell (moves tpot_p90_ms)."""
from bench.readers import decode_step_ms as read  # noqa: F401
