"""preemptions: ``Engine.preempt_count`` (``metrics()["preemptions"]``)
at the window's close minus at its opening."""


def read(run):
    return run.preemptions
