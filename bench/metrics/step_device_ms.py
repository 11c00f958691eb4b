"""step_device_ms: mean device time of one run of the paged step program
(``jit_paged_step`` in the trace's ``XLA Modules`` line)."""


def read(run):
    if run.trace is None or not run.trace["step_s"]:
        return None
    v = run.trace["step_s"]
    return 1e3 * sum(v) / len(v)
