"""Seconds from process start to the first timed request or job: graph
generation, weights, engine build, compile-cache loads and warm-up."""


def read(rec):
    return rec["setup_s"]
