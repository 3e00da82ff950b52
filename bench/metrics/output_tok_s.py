"""Tokens emitted in the window over its length: the decode ticks' useful
tokens (the engine's ``useful_decoded`` counter, between the window's two
snapshots) plus the first tokens installed in the window."""


def read(run):
    c = run.cell
    decoded = c.counters1["useful_decoded"] - c.counters0["useful_decoded"]
    firsts = sum(1 for s in c.served
                 if s.t_first is not None and c.t0 <= s.t_first <= c.t1)
    return (decoded + firsts) / run.window_s
