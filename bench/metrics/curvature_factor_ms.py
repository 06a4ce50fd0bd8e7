"""curvature_factor_ms (ms): per update, the device time of the
operations traced under the ``curvature_factor`` scope
(``core/curvature.py`` ``make_curvature_ops``: the logit-space curvature
factors built once per CG stage, before the solves; for the lattice
losses, the forward on the curvature batch and the lattice gradients
G_mpe and G_mmi), averaged over the devices.  The scope lies inside
``curvature_product``, so its time is part of ``curvature_product_ms``."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "curvature_factor")
    return None if t is None else 1e3 * t
