"""Time the calling thread spent in Python's garbage collector
(``hntl.gc`` spans) over the traced window, per search call.  0.0 where
no collection ran; None where the program writes no spans at all."""
from chipbench.stages import GC, ms_per_call


def read(view):
    return ms_per_call(view, GC)
