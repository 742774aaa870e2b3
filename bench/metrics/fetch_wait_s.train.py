"""Seconds per window step that the executor waited for weights at its
fetches (``fetch_wait_s`` of ``OffloadSession.train_step``)."""


def read(record):
    steps = record.get("window_steps")
    if not steps:
        return None
    return sum(m["fetch_wait_s"] for m in steps) / len(steps)
