"""XLA programs compiled inside the window: the change of the
``jax.compile.count`` listener's counter.  Should be 0."""


def read(r):
    return r.compiles_in_window
