"""Device time of the fused scan→select kernel per query completed in the
traced window (the kernel's events as ``names.is_kernel`` finds them)."""
from chipbench.names import is_kernel


def read(view):
    t = view.op_time(is_kernel)
    if t <= 0 or view.queries == 0:
        return None
    return t / view.queries * 1e6
