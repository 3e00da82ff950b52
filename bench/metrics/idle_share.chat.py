"""Idle share of the device in the chat cell (moves tpot_p90_ms)."""
from bench.readers import idle_share as read  # noqa: F401
