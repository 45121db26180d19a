"""The tiered cache's cascade lookup (hot top-k + centroid probe + IVF
bucket gather + tail scan + tenant-masked merge): the served jnp form
(`ref.py`) and the Pallas kernel it is the reference for (`kernel.py`),
chosen in `ops.py`.  See DESIGN.md §3 for the dataflow."""
from repro.kernels.cascade_lookup.ops import cascade_lookup

__all__ = ["cascade_lookup"]
