"""cg_vector_ms (ms): per update, the device time of the operations traced
under the ``cg_solve`` scope (``core/cg.py``) and under neither
``curvature_product`` nor ``candidate_eval``: the solves' vector work
(step lengths, axpys, preconditioning, dot products, candidate
selection), averaged over the devices."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "cg_solve",
                besides=("curvature_product", "candidate_eval"))
    return None if t is None else 1e3 * t
