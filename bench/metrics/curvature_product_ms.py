"""curvature_product_ms (ms): per update, the device time of the
operations traced under the ``curvature_product`` scope
(``core/curvature.py``: every Fisher and Gauss-Newton product, of the
inner NG solve and the outer solve alike), averaged over the devices."""

from bench.stages import stage_s


def read(run):
    t = stage_s(run, "curvature_product")
    return None if t is None else 1e3 * t
