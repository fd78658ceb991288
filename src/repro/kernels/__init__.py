# Pallas TPU kernels for the paper's trace-replay hot spots (DESIGN.md §3):
#   evict_argmin        — the eviction decision of every priority policy
#   interval_occupancy  — eq. (2) occupancy profile (blocked prefix sum)
#   occupancy_feasible  — fused range-add scan + running-max cap check of
#                         cost-FOO's rounded schedule (DESIGN.md §4)
# Each has a pallas_call implementation, a jit'd wrapper in ops.py and a
# pure-jnp oracle in ref.py; tests sweep shapes/dtypes against the oracle.
from . import ops, ref
from .ops import evict_argmin, interval_occupancy, occupancy_feasible

__all__ = ["ops", "ref", "evict_argmin", "interval_occupancy",
           "occupancy_feasible"]
