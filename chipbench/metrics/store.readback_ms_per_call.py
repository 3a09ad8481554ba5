"""What reading a call's results back costs beyond waiting for its
program: each ``hntl.search.readback`` span (``jax.device_get``) less the
device-busy time inside it, per search call."""
from chipbench.stages import READBACK, ms_per_call


def read(view):
    return ms_per_call(view, READBACK, beyond_device=True)
