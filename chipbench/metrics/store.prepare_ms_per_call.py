"""Host time per search call before its first program is dispatched
(``hntl.search.prepare``): snapshot, argument checks, the plane-cache and
liveness lookups, the upload of the queries and filter scalars."""
from chipbench.stages import PREPARE, ms_per_call


def read(view):
    return ms_per_call(view, PREPARE)
