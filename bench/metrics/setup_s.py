"""Seconds from process start to the first timed submit: weight init,
program compile or load, and the warm-up request."""


def read(rec):
    return rec["setup_s"]
