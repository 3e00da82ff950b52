"""Wait from due to admission, p90 over every request due in the window of
the chat cell, in ms (``program_readers.queue_wait_ms``): the part of
``ttft_p90_ms.chat`` spent queued, before the prefill starts."""
from bench.program_readers import queue_wait_ms
from bench.readers import p90


def read(run):
    waits = queue_wait_ms(run)
    return None if waits is None else p90(waits)
