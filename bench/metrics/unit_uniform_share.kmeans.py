"""unit_uniform_share.kmeans (%): the share of the division unit's kernel
time spent dividing a plane by one number without broadcasting it: unit
kernels (``kernels.is_unit``) under the scope ``dm.div.uniform_divisor``
(core/division_modes.py ``div``) over all unit kernels. 0 where the
program divides by a broadcast plane; nothing to read (None) where no
traced operation carries a scope or no unit kernel ran."""
from bench.lib import kernels, scopes

UNIFORM = r"dm\.div\.uniform_divisor"


def read(view):
    t = view.trace
    paths = scopes.of_view(view)
    if not scopes.scoped(t, paths):
        return None
    total = t.op_s(kernels.is_unit)
    if not total:
        return None
    uniform = t.op_s(lambda name: kernels.is_unit(name)
                     and scopes.under(paths.get(name, ""), (UNIFORM,)))
    return 100.0 * uniform / total
