"""Share of the traced window in which no op ran on the device: one minus
the union of op intervals over the window, averaged over the cell's chips."""


def read(r):
    if r.trace is None or not r.trace.device_ids(r.devices):
        return None
    return 100.0 * (1.0 - r.trace.busy_s(r.devices) / r.out.window_s)
