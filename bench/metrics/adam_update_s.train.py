"""Seconds per window step of host Adam's arithmetic on the optimizer
thread: gradient unscale, the update, and the write-back casts
(``adam_update_s`` of ``OffloadSession.train_step``; none where the
program has no such counter)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "adam_update_s" not in steps[0]:
        return None
    return sum(m["adam_update_s"] for m in steps) / len(steps)
