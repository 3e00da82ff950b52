"""Time to first token, p90 over every request due in the window of the chat
cell, from when it was due (``readers.ttft_ms``).  A per-layer reading: a
window of the chat cell holds 40 requests, so p90 rests on four, and a stall
of the machine that meets two of them moves it by half."""
from bench.readers import p90, ttft_ms


def read(run):
    return p90(ttft_ms(run))
