"""Seconds from process start to the first timed request: loading,
weights made from the seed, compiling or loading every program the
window runs, and warming them up."""


def read(record):
    return record.setup_s
