"""Seconds per window step that host Adam's state reads waited for a free
buffer of the two-buffer staging arena (``adam_arena_wait_s`` of
``OffloadSession.train_step``; none where the program has no such
counter)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "adam_arena_wait_s" not in steps[0]:
        return None
    return sum(m["adam_arena_wait_s"] for m in steps) / len(steps)
