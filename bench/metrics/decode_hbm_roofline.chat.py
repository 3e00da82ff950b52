"""The decode ticks' share of their roofline in the chat cell (moves
tpot_p90_ms)."""
from bench.readers import decode_roofline as read  # noqa: F401
