"""The decode ticks' share of their roofline in the batch cell (moves
output_tok_s)."""
from bench.readers import decode_roofline as read  # noqa: F401
