"""How the division unit's kernels are named in a device trace.

Each pattern is a regular expression matched against an operation's short
name on the device's ``XLA Ops`` line (see lib/trace.py). A Pallas kernel
appears as a custom call named after the jitted function that launches it,
numbered per call site: ``%tsdiv_divide_tiled_2d.12``, ``%softmax_2d.7``,
``%rmsnorm_2d.16`` (TPU v5e trace, JAX 0.9).
"""
from __future__ import annotations

from .trace import matcher

# kernels/tsdiv.py: the fused divide, reciprocal and rsqrt kernels
TSDIV = (r"^%tsdiv_(divide|recip|rsqrt)(_tiled)?_2d(\.\d+)?$",)
# kernels/softmax.py and kernels/rmsnorm.py: the consumers of the unit
SOFTMAX = (r"^%softmax_2d(\.\d+)?$",)
RMSNORM = (r"^%rmsnorm_2d(\.\d+)?$",)
UNIT = TSDIV + SOFTMAX + RMSNORM

is_tsdiv = matcher(TSDIV)
is_softmax = matcher(SOFTMAX)
is_rmsnorm = matcher(RMSNORM)
is_unit = matcher(UNIT)
