"""Seconds of XLA compilation during set-up: ``jax.compile.ms`` up to the
first timed request."""


def read(r):
    return r.setup_compile_s
