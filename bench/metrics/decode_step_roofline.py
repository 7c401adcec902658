"""Share of the roofline of the window's decode steps (%): the least time
each step could take, by its operations or by its bytes (weights, keys and
values of valid positions, logits), over its device time."""
from bench import derive


def read(run):
    return derive.decode_roofline(run)
