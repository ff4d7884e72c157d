"""Layer: epoch block. Programs compiled inside the measured window, from
the `core/programs` registry. Set-up warms every shape, so this is 0; a
change that makes it otherwise compiles inside the window."""


def read(record):
    value = record.recorder.counters.get("window.compiles")
    return None if value is None else float(value)
