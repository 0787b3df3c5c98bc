"""tsdiv_roofline.kmeans (%): the divide kernel's share of its roofline.

Needed bytes of every divide a Lloyd call makes (per iteration: the
(N, K) distance plane over the scalar D, the (K, D) centroid sums over the
(K, 1) counts, and the scalar inertia; then the final assignment's plane
and inertia once more): 4 bytes read and 4 written per quotient element,
plus each divisor element once. This counts the work, not what the kernel
reads today (it reads a divisor broadcast to a full plane), so a program
that stops materializing the broadcast is credited. The share is those
bytes over the HBM bandwidth, over the device time of the tsdiv kernels in
the trace (both per chip, mean over chips).
"""
from bench.lib import kernels


def needed_bytes_per_call(work, iters_per_call: int) -> float:
    n, k, d = work["points"], work["clusters"], work["dim"]
    plane = 8.0 * n * k + 4.0
    centroids = 8.0 * k * d + 4.0 * k
    inertia = 12.0
    return iters_per_call * (plane + centroids + inertia) + plane + inertia


def read(view):
    w, t = view.work, view.trace
    secs = t.op_s(kernels.is_tsdiv) if t is not None else 0.0
    if not secs or not w.get("calls"):
        return None
    per_call = needed_bytes_per_call(w, view.traffic["iters_per_call"])
    bytes_per_chip = w["calls"] * per_call / w["chips"]
    return 100.0 * bytes_per_chip / view.peaks["hbm_bytes_per_s"] / secs
