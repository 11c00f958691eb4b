"""admit_wait_p95_ms: 95th percentile, over the requests due in the
window, of the time from arrival to the engine's own admission stamp
(``Request.admitted_at``). The engine stamps submission and admission on
the harness's clock (``time.perf_counter``), so the wait is the engine's
``admitted_at - submitted_at`` plus the harness's lateness in submitting
it. One never admitted counts with the time it waited until it was given
up, a bound from below. A program without the stamps reads nothing."""
from harness import percentile


def read(run):
    due = run.due()
    if not any(getattr(r.req, "admitted_at", None) is not None for r in due):
        return None
    end = run.ticks[-1].end if run.ticks else run.window_s
    vals = []
    for r in due:
        at = getattr(r.req, "admitted_at", None)
        if at is None:
            vals.append(end - r.sent)
        else:
            vals.append(at - r.req.submitted_at + r.lateness())
    v = percentile([max(0.0, x) for x in vals], 95)
    return None if v is None else v * 1e3
