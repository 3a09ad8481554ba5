"""Host time per search call: each call span less the device-busy time
inside it, averaged over the traced calls."""


def read(view):
    if not view.spans:
        return None
    host = [s.dur - view.busy_s(s.start, s.end) for s in view.spans]
    return sum(host) / len(host) * 1e3
