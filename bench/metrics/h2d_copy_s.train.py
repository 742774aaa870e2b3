"""Seconds per window step of host-to-device weight, checkpoint and KV
copies, each until it landed (``h2d_copy_s`` of
``OffloadSession.train_step``; none where the program has no such
counter)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "h2d_copy_s" not in steps[0]:
        return None
    return sum(m["h2d_copy_s"] for m in steps) / len(steps)
