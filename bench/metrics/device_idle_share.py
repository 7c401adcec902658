"""Share of the traced window in which no operation ran on the device (%)."""
from bench import derive


def read(run):
    return derive.idle_share(run)
