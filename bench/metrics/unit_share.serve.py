"""unit_share.serve (%): the division unit's kernels' share of device busy
time in serving (softmax, rmsnorm and tsdiv kernels)."""
from bench.lib import kernels


def read(view):
    t = view.trace
    if t is None or not t.busy_s:
        return None
    return 100.0 * t.op_s(kernels.is_unit) / t.busy_s
