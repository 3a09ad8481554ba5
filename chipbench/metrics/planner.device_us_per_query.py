"""Device time of the search program's other ops (routing, per-probe
projection, Mode B re-rank, top-k) per query in the traced window."""
from chipbench.names import in_search, is_kernel


def read(view):
    t = view.op_time(lambda e: in_search(e) and not is_kernel(e))
    if t <= 0 or view.queries == 0:
        return None
    return t / view.queries * 1e6
