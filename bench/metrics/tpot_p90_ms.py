"""Time per output token, (t_done - t_first) / (tokens - 1), p90 over the
window's served requests of more than one token."""
from bench.readers import p90


def read(run):
    return p90([1e3 * (s.t_done - s.t_first) / (len(s.result) - 1)
                for s in run.cell.window_requests()
                if s.result is not None and len(s.result) > 1])
