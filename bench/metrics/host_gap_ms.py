"""host_gap_ms (ms): the mean idle gap on a device between the end of one
update's program and the start of the next's, over the traced window."""


def read(run):
    if run.window is None:
        return None
    gaps = run.host_gaps_s()
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
