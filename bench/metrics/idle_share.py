"""Device idle share (%): 1 - the union of device-operation intervals over
the traced window, on the idlest chip of the cell."""


def read(summary, run):
    idle = summary.max_idle_share()
    return None if idle is None else 100.0 * idle
