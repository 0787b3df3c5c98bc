"""device_idle_share.kmeans (%): the share of the traced window in which no
operation ran on the device, mean over chips."""


def read(view):
    t = view.trace
    if t is None or not t.n_devices:
        return None
    return 100.0 * t.idle_share
