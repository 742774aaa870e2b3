"""Nanoseconds of host Adam's arithmetic per entry updated, over the
window steps: the sum of ``adam_update_s`` x 1e9 over the sum of
``adam_update_elems`` (both from ``OffloadSession.train_step``; none where
the program has no such counter or updated nothing)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "adam_update_elems" not in steps[0]:
        return None
    elems = sum(m["adam_update_elems"] for m in steps)
    if not elems:
        return None
    return sum(m["adam_update_s"] for m in steps) * 1e9 / elems
