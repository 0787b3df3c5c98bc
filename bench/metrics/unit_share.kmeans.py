"""unit_share.kmeans (%): the division unit's kernels' share of device busy
time (tsdiv, softmax and rmsnorm kernels; K-Means runs only tsdiv)."""
from bench.lib import kernels


def read(view):
    t = view.trace
    if t is None or not t.busy_s:
        return None
    return 100.0 * t.op_s(kernels.is_unit) / t.busy_s
