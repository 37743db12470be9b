"""`collective_exposed_pct.gang`: share of the time inside the program's `gang.solve` spans in which an all-reduce runs on a device and no other op does, on the device where it is largest."""
from bench.mesh import collective_exposed_pct as read  # noqa: F401
