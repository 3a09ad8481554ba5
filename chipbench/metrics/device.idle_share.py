"""1 minus the union of the device's busy intervals over the traced
window."""


def read(view):
    if view.window_s <= 0 or not view.ops:
        return None
    return 1.0 - view.busy_s() / view.window_s
