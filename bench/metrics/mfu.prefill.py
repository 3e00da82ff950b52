"""Needed prefill FLOPs (the head at each prompt's last position only) over
the chunk programs' device time times the chip's peak, in %."""
from bench.readers import PREFILL_PROGRAMS, prefill_totals, program_s


def read(run):
    s = program_s(run, PREFILL_PROGRAMS)
    _, flops = prefill_totals(run)
    if s is None or flops == 0:
        return None
    return 100.0 * flops / (s * run.peak["bf16_flops_per_s"])
