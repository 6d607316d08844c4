"""Set-up: process start to the measured window's opening (loading,
data, server start, warm-up and compilation or cache loads)."""


def read(run):
    return run.setup_s
