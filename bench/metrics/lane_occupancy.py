"""Share of the decode lanes that emitted a useful token, over the window's
decode steps, in %: the engine counters' useful tokens over steps times
``max_batch``."""


def read(run):
    c = run.cell
    steps = c.counters1["decode_steps"] - c.counters0["decode_steps"]
    if steps == 0:
        return None
    useful = c.counters1["useful_decoded"] - c.counters0["useful_decoded"]
    return 100.0 * useful / (steps * c.ecfg.max_batch)
