"""Seconds per request in the session's verdict span (accuracy and adder extraction)."""


def read(run):
    return run.span_mean("verdict")
