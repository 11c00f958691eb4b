"""out_tok_per_s: every output token the window's ticks produced, over the
window's length (host clock)."""


def read(run):
    if run.window_s <= 0:
        return None
    toks = sum(t.emitted for t in run.ticks if t.start < run.window_s)
    return toks / run.window_s
