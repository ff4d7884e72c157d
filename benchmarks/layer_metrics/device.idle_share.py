"""Layer: device. Share of the traced window in which no operation ran on
the device: 1 - busy / window, both from the device trace."""


def read(record):
    if record.trace is None or not record.trace.window_s:
        return None
    return 100.0 * (1.0 - record.trace.busy_s / record.trace.window_s)
