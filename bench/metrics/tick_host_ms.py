"""tick_host_ms: mean, over the traced ticks, of the ``bench.tick`` span's
length minus the device's busy time inside it: the host's part of a tick
(scheduling, building inputs, dispatch, the token fetch)."""


def read(run):
    if run.trace is None or not run.trace["tick_host_s"]:
        return None
    v = run.trace["tick_host_s"]
    return 1e3 * sum(v) / len(v)
