"""Blocked shortest-transfer batch-costing pass (jax-free import).

``st_cost`` is the host-facing op the jitted ``shortesttransfer`` broker
calls once per dispatch batch; ``st_cost_ref`` the float64 oracle;
``st_cost_dense_ref`` the pre-blocked O(sites x files x sites)
formulation kept only for the bit-identity tests. Importing this package
pulls no jax — ``ops`` loads it lazily per call.
"""

from ..spec import ST_COST_SPEC as SPEC
from .ops import st_cost
from .ref import st_cost_dense_ref, st_cost_ref

__all__ = ["SPEC", "st_cost", "st_cost_ref", "st_cost_dense_ref"]
