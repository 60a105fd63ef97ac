"""Programs compiled inside the window, from ``jax.monitoring``: shapes the
warm-up did not meet.  The window keeps the persistent cache off, so each
is a full compile in every run; it should be 0."""


def read(run):
    return run.compiles
