"""unit_operand_share.kmeans (%): device time under the division unit's
scopes (``dm.*``, core/division_modes.py) that is not one of its kernels,
over busy time: the operand broadcasts, casts and copies the unit stages
around its kernels. Nothing to read (None) where no traced operation
carries a scope."""
from bench.lib import scopes


def read(view):
    return scopes.busy_share(view.trace, scopes.of_view(view),
                             inside=(scopes.UNIT,), unit_kernels=False)
