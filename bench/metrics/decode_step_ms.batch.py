"""Device milliseconds per decode step in the batch cell (moves output_tok_s)."""
from bench.readers import decode_step_ms as read  # noqa: F401
