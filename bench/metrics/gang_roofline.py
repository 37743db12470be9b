"""`gang_roofline`: per-device least HBM bytes of the gang solve (``bench.mesh.least_bytes_per_device``, the driver's ``least_bytes_per_solve``) over device busy time per solve in the solve spans, averaged over the devices, as a share of 819 GB/s."""
from bench.layers import solve_roofline as read  # noqa: F401
