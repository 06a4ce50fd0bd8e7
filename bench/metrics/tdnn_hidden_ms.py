"""tdnn_hidden_ms (ms): per update, the device time of the operations
traced under the ``tdnn_hidden`` scope (``models/acoustic.py``: the TDNN's
five spliced layers' dots and activations at their stated precision, with
their JVPs and transposes, wherever the update runs them), averaged over
the devices.  It cuts across the update stages: its time is part of
theirs."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "tdnn_hidden")
    return None if t is None else 1e3 * t
