"""rows_per_tick: mean of ``Engine.tick()``'s return value (request rows
advanced) over the window's ticks."""


def read(run):
    rows = [t.rows for t in run.ticks if t.start < run.window_s]
    return sum(rows) / len(rows) if rows else None
