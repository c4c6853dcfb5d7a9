"""Bytes per request handed to the device before the forward: the
``h2d_bytes`` of the program's ``execute.stage`` span (features and edge
arrays)."""

import program_trace


def read(run):
    return program_trace.per_request(run, "execute.stage", attr="h2d_bytes")
