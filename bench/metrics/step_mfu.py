"""step_mfu (%): the matmul FLOPs the NGHF update requires
(``bench/flops/``), times the updates of the traced window, over the
window's length times the chips times one chip's peak (``peaks.json``)."""

from bench.peaks import peaks


def read(run):
    if run.window is None or run.busy_s() is None:
        return None             # no device ran anything: nothing to read
    flops = run.update_flops * run.updates
    peak = peaks(run.device_kind)["matmul_flops_per_s"]
    return 100.0 * flops / (run.window_s() * run.chips * peak)
