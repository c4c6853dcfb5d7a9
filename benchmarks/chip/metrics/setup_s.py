"""Set-up: process start to the first timed request (JAX start, training
the weights, generating the design, the warm-up request and its compiles)."""


def read(run):
    return run.setup_s
