"""lloyd_mfu (%): one Lloyd iteration's share of the chip's peak.

The least time an iteration could take is the larger of its needed
operations over the bf16 peak (the only published matrix peak; the
program's f32 products at precision highest run as several bf16 passes)
and its needed bytes over the HBM bandwidth; the share is that least time
over the measured time per iteration (the window over its iterations).
Needed per iteration: 4 N K D operations (distances 2 N K D, centroid sums
2 N K D); the points read twice (assignment and update) and the centroids
once, 4 (2 N D + K D) bytes. Over a mesh the peaks are summed.
"""


def needed_flops(work) -> float:
    return 4.0 * work["points"] * work["clusters"] * work["dim"]


def needed_bytes(work) -> float:
    n, k, d = work["points"], work["clusters"], work["dim"]
    return 4.0 * (2 * n * d + k * d)


def read(view):
    w, p = view.work, view.peaks
    if not w.get("iters"):
        return None
    t_iter = w["window_s"] / w["iters"]
    t_min = max(needed_flops(w) / (p["bf16_flops_per_s"] * w["chips"]),
                needed_bytes(w) / (p["hbm_bytes_per_s"] * w["chips"]))
    return 100.0 * t_min / t_iter
