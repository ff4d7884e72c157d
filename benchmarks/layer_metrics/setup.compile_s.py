"""Layer: set-up, compile. What the `core/programs` registry charged to
compilation before the window: the wall of every first dispatch of a shape
(trace, lower, and compile or load from the persistent cache). Small, not 0,
when the cache serves every program."""


def read(record):
    ms = record.recorder.counters.get("setup.compile_ms")
    return None if ms is None else ms / 1e3
