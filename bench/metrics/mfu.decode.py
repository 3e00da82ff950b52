"""The decode ticks' share of the chip's peak FLOPs in the batch cell (moves
output_tok_s)."""
from bench.readers import decode_mfu as read  # noqa: F401
