"""Layer: trainer host. Host time of one `trainer.train()` call in which the
device did nothing: the span around the call minus the device-busy time
inside it, per call. It is what `models/trainer.py` spends on init, the
per-call `pos_weight` loop over every slot, the dispatch and the loss fetch.
A difference, taken from outside; the spans inside `train()` (PR 26) give the
same time by its parts: `trainer.span_host_ms_per_call` and its siblings."""


def read(record):
    calls = record.recorder.named("refresh.call")
    if record.trace is None or not calls:
        return None
    return (record.trace.window_s - record.trace.busy_s) * 1e3 / len(calls)
