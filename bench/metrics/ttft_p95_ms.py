"""ttft_p95_ms: 95th percentile, over every request due in the window, of
the time from its scheduled arrival (open loop) or its send (closed loop)
to the end of the tick that produced its first token. A request that got
no first token counts with the time it waited until it was given up, a
bound from below."""
from harness import percentile


def read(run):
    due = run.due()
    end = run.ticks[-1].end if run.ticks else run.window_s
    vals = [r.ttft() if r.token_times else max(0.0, end - r.sent) for r in due]
    v = percentile(vals, 95)
    return None if v is None else v * 1e3
