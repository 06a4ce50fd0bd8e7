"""device_idle_share (%): the share of the traced window in which no
operation ran on a device, averaged over the devices."""


def read(run):
    if run.window is None:
        return None
    idle = run.idle_share()
    return None if idle is None else 100.0 * idle
