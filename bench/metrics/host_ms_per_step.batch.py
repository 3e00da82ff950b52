"""Host milliseconds per engine step not blocked on a device read, batch
cell (moves output_tok_s)."""
from bench.program_readers import host_ms_per_step as read  # noqa: F401
