"""slo_pct: share of the requests due in the window that finished with a
time to first token within ``limits.ttft_ms`` (``base`` plus
``per_prompt_token`` times the prompt's length) and a mean gap between
their tokens within ``limits.itl_ms``. A refused, failed or unfinished
request misses."""


def read(run):
    due = run.due()
    if not due:
        return None
    lim = run.limits
    met = 0
    for r in due:
        if not r.done or not r.token_times:
            continue
        ttft_ms = r.ttft() * 1e3
        gaps = r.gaps()
        mean_gap_ms = 1e3 * sum(gaps) / len(gaps) if gaps else 0.0
        ttft_lim = (lim["ttft_ms"]["base"]
                    + lim["ttft_ms"]["per_prompt_token"] * len(r.planned.prompt))
        if ttft_ms <= ttft_lim and mean_gap_ms <= lim["itl_ms"]:
            met += 1
    return 100.0 * met / len(due)
