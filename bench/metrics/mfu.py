"""Useful operations of every prefill and decode step in the window over the
traced window, as a share of the chip's peak (%)."""
from bench import derive


def read(run):
    return derive.mfu(run)
