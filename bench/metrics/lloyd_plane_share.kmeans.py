"""lloyd_plane_share.kmeans (%): device time under ``kmeans.distance`` or
``kmeans.assign`` (workloads/kmeans.py) and outside the division unit's
scopes, over busy time: the XLA passes over the (N, K) distance plane
around the divide kernel (the distance fusion, argmin and min). Nothing to
read (None) where no traced operation carries a scope."""
from bench.lib import scopes


def read(view):
    return scopes.busy_share(view.trace, scopes.of_view(view),
                             inside=(scopes.DISTANCE, scopes.ASSIGN),
                             outside=(scopes.UNIT,))
