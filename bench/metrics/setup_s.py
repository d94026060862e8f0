"""Set-up time: process start until the measured window opens (weights
from the seed, compiles or cache loads, warm-up traffic)."""


def read(run):
    return run.window_open - run.t_start
