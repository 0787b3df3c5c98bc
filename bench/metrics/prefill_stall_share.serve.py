"""prefill_stall_share.serve (%): the share of the window in which the
serving loop admitted requests (prefill, cache insert, first token) while
the other slots waited for their next decode step; from the benchmark's
host spans around the engine's prefill and decode calls."""


def read(view):
    w = view.work
    if not w.get("window_s"):
        return None
    return 100.0 * w["stall_s"] / w["window_s"]
