"""Host milliseconds per engine step not blocked on a device read, chat
cell (moves tpot_p90_ms)."""
from bench.program_readers import host_ms_per_step as read  # noqa: F401
