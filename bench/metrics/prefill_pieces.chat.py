"""Engine steps per completed prefill in the chat cell's window: how many
pieces the prefill budget cut a prompt into (moves tpot_p90_ms)."""
from bench.program_readers import prefill_pieces as read  # noqa: F401
