"""Seconds per window step that host Adam's write-backs of master, moments
and compute copy spent in the store, on the write-back thread
(``adam_write_s`` of ``OffloadSession.train_step``; none where the
program has no such counter)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "adam_write_s" not in steps[0]:
        return None
    return sum(m["adam_write_s"] for m in steps) / len(steps)
