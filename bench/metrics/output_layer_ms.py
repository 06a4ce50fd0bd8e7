"""output_layer_ms (ms): per update, the device time of the operations
traced under the ``output_layer`` scope (``models/acoustic.py``: the
TDNN's 1000 -> 6000 output layer, its JVPs and transposes, wherever the
update runs it), averaged over the devices.  It cuts across the update
stages: its time is part of theirs."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "output_layer")
    return None if t is None else 1e3 * t
