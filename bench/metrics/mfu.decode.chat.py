"""The decode ticks' share of the chip's peak FLOPs in the chat cell (moves
tpot_p90_ms)."""
from bench.readers import decode_mfu as read  # noqa: F401
