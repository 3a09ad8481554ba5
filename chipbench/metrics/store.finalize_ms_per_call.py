"""Host time per search call after its last read-back
(``hntl.search.finalize``): the memtable scan, the final top-k sort on the
host and the upload of the result."""
from chipbench.stages import FINALIZE, ms_per_call


def read(view):
    return ms_per_call(view, FINALIZE)
