"""Set-up seconds: process start to the first timed step (host clock).

JAX start-up, building and compiling the step (or loading it from the
persistent cache), making the state on the device, and the first steps
that warm every shape and give the correctness readings."""


def read(run):
    return run.setup_s
