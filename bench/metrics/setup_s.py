"""setup_s: process start to the window's opening (host clock): TPU start,
the engine and its step program, weights from the seed, the warm-up."""


def read(run):
    return run.setup_s
