"""Seconds per window step that the optimizer thread waited at the end of
each unit for the unit's write-backs to land (``adam_commit_wait_s`` of
``OffloadSession.train_step``; none where the program has no such
counter)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "adam_commit_wait_s" not in steps[0]:
        return None
    return sum(m["adam_commit_wait_s"] for m in steps) / len(steps)
