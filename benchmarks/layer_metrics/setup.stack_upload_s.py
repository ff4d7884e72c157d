"""Layer: set-up, stack. Wall of `stacked.stack_dataset`: the padded host
copy of every slot and its upload, to the point where the device holds it."""


def read(record):
    spans = record.recorder.named("setup.stack_upload")
    return sum(s.seconds for s in spans) if spans else None
