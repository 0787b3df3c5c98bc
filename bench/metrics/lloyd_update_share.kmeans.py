"""lloyd_update_share.kmeans (%): device time under ``kmeans.update``
(workloads/kmeans.py) and outside the division unit's scopes, over busy
time: the one-hot, counts, block sums and, on a mesh, their exchange.
Nothing to read (None) where no traced operation carries a scope."""
from bench.lib import scopes


def read(view):
    return scopes.busy_share(view.trace, scopes.of_view(view),
                             inside=(scopes.UPDATE,), outside=(scopes.UNIT,))
