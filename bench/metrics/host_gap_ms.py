"""Mean device-idle milliseconds between consecutive device programs in the
traced window: the host's time between launches while slots are busy."""
from bench import derive


def read(run):
    return derive.program_gap_ms(run)
