"""queue_wait_p95_ms: 95th percentile, over the requests due in the
window, of the time from arrival to the start of the tick whose admission
put the request into ``Engine.admission_log``. One never admitted counts
with the time it waited until it was given up, a bound from below."""
from harness import percentile


def read(run):
    end = run.ticks[-1].end if run.ticks else run.window_s
    vals = [(r.admitted if r.admitted is not None else end) - r.sent
            for r in run.due()]
    v = percentile([max(0.0, x) for x in vals], 95)
    return None if v is None else v * 1e3
