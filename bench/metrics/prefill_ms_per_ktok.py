"""Device milliseconds of the prefill chunk programs per 1000 real prompt
tokens prefilled in the traced window."""
from bench.readers import PREFILL_PROGRAMS, prefill_totals, program_s


def read(run):
    s = program_s(run, PREFILL_PROGRAMS)
    toks, _ = prefill_totals(run)
    if s is None or toks == 0:
        return None
    return 1e3 * s / (toks / 1e3)
