"""itl_p95_ms: 95th percentile of every gap between two consecutive output
tokens of a request, pooled over the requests due in the window: an open
loop's followed after it closes, a closed loop's within the window."""
from harness import percentile


def read(run):
    gaps = []
    for r in run.due():
        t = r.token_times if run.open_loop else [x for x in r.token_times if x >= 0]
        gaps.extend(b - a for a, b in zip(t, t[1:]))
    v = percentile(gaps, 95)
    return None if v is None else v * 1e3
