"""Seconds from process start to the start of the window."""


def read(rec):
    return rec.setup_s
