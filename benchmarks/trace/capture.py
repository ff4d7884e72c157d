"""Start and stop the JAX profiler for the traced window.

The Python tracer is off: it writes an event per Python call, slows the host
it measures and is read by nothing here. Host `TraceAnnotation`s (the
benchmark's spans) and the device's own events are kept.
"""
from __future__ import annotations


def start(directory: str) -> None:
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=options)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()
