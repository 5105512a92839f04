# Pallas TPU kernels for the compute hot-spots (compiled on a TPU v5e,
# interpreted elsewhere).  ops.py = jit wrappers, ref.py = jnp oracles.
from . import ops, ref

__all__ = ["ops", "ref"]
