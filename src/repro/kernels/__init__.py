"""The CoMeFa kernels: the simulator-backed compute kernels (`comefa_sim`),
the bit-packed simulator step kernel they run on (`comefa_step`), and the
pure-numpy/jnp references the tests compare them with (`ref`)."""
from . import comefa_sim, comefa_step, ref

__all__ = ["comefa_sim", "comefa_step", "ref"]
