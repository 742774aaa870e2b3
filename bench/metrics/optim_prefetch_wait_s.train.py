"""Seconds per window step that host Adam waited for its state to arrive
from the store (``optim_prefetch_wait_s`` of
``OffloadSession.train_step``)."""


def read(record):
    steps = record.get("window_steps")
    if not steps:
        return None
    return sum(m["optim_prefetch_wait_s"] for m in steps) / len(steps)
