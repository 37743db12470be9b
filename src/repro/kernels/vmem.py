"""The one VMEM budget shared by every Pallas kernel and ``ops``' block pickers.

A TPU v5e TensorCore has 128 MiB of VMEM, but Mosaic only lets a kernel
allocate up to its *scoped* limit, 16 MiB by default. Every ``pallas_call``
in this package passes ``COMPILER_PARAMS``, which raises that limit to
``VMEM_LIMIT_BYTES``; ``ops.pick_block_m`` and ``ops.resident_fits`` size
blocks and route tiers against the same number, counting what Mosaic
actually allocates (see ``ops.streamed_vmem_bytes`` /
``ops.resident_vmem_bytes``). Half the physical VMEM leaves the rest to
Mosaic's internal scratch and to the compiler's own fusions.
"""
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT_BYTES = 64 * 1024 * 1024
COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
