"""collective_exposed_ms (ms): per update, the time of the all-reduce,
all-gather and other collective operations on a device during which no
other operation runs there, averaged over the devices."""


def read(run):
    if run.window is None:
        return None
    exposed = run.collective_exposed_s()
    return None if exposed is None else 1e3 * exposed / run.updates
