"""Seconds of XLA backend compilation during set-up, as ``jax.monitoring``
reports them; a program found in the persistent cache adds only its load."""


def read(run):
    return run.compile_s
