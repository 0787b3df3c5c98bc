"""collective_share.kmeans (%): device time in collectives during which no
other operation ran on that chip, over busy time, mean over chips. Nothing
to read (None) where the trace holds no collective."""
from bench.lib import trace as tr


def read(view):
    t = view.trace
    if t is None or not t.busy_s:
        return None
    if not t.op_count(lambda n: bool(tr.COLLECTIVE.search(n))):
        return None
    return 100.0 * t.exposed_collective_s() / t.busy_s
