"""From process start to the start of the window: weights, calibration,
int8 conversion, scheduler, and the warm-up that compiles or loads every
program the window drives."""


def read(run):
    return run.setup_s
