"""Seconds per request in garbage collection: the program's ``gc`` spans
(one per collection, on the thread that triggered it) in the window."""

import program_trace


def read(run):
    return program_trace.per_request(run, "gc")
