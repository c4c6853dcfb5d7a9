"""Seconds per request obtaining the aggregation pair: the program's
``execute.plan`` span (the plan cache's structural key hash, then a hit or
a build)."""

import program_trace


def read(run):
    return program_trace.per_request(run, "execute.plan")
