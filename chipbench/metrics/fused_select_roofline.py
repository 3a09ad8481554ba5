"""The scan→select kernel's share of its roofline, in percent: the least
time the chip could take for the traced calls' work (``roofline``), over
the kernel's device time."""
from chipbench.names import is_kernel
from chipbench.roofline import least_time


def read(view):
    t = view.op_time(is_kernel)
    if t <= 0 or not view.work or not view.peaks:
        return None
    least, _ = least_time(view.work["ops"], view.work["bytes"], view.peaks)
    return least / t * 100.0
