"""Seconds per window step that host Adam spent reading its state from
the store and upcasting it, on the state-prefetch thread (``adam_read_s`` of
``OffloadSession.train_step``; none where the program has no such
counter)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "adam_read_s" not in steps[0]:
        return None
    return sum(m["adam_read_s"] for m in steps) / len(steps)
