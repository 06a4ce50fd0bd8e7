"""candidate_eval_ms (ms): per update, the device time of the operations
traced under the ``candidate_eval`` scope (``core/curvature.py``
``eval_loss``: every CG candidate's evaluation and the zero update's),
averaged over the devices."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "candidate_eval")
    return None if t is None else 1e3 * t
