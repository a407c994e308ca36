"""Share of the traced window with no operation on the device, in %
(mean over the devices used)."""


def read(run):
    if run.reduced is None or run.reduced.window_s <= 0:
        return None
    busy = sum(run.reduced.busy_s) / len(run.reduced.busy_s)
    return (1.0 - busy / run.reduced.window_s) * 100.0
