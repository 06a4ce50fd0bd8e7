"""tdnn_splice_ms (ms): per update, the device time of the operations
traced under the ``tdnn_splice`` scope (``models/acoustic.py``: the
TDNN's context gathers and concatenations, their JVPs and transposes),
averaged over the devices.  It cuts across the update stages: its time
is part of theirs."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "tdnn_splice")
    return None if t is None else 1e3 * t
