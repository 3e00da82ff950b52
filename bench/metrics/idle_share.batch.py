"""Idle share of the device in the batch cell (moves output_tok_s)."""
from bench.readers import idle_share as read  # noqa: F401
