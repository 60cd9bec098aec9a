"""Programs lowered inside the traced window (JAX's compile events),
which should be none: every shape is warmed before the window opens."""


def read(summary, run):
    return run["compiles_in_window"]
