"""Host time per search call inside the jitted search program's call
(``hntl.search.dispatch``), until it returns: JAX's dispatch of the
program with its plane pytree, before the device has finished."""
from chipbench.stages import DISPATCH, ms_per_call


def read(view):
    return ms_per_call(view, DISPATCH)
