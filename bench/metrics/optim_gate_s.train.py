"""Seconds per window step that the forward waited for the previous
step's host Adam (``optim_gate_s`` of ``OffloadSession.train_step``)."""


def read(record):
    steps = record.get("window_steps")
    if not steps:
        return None
    return sum(m["optim_gate_s"] for m in steps) / len(steps)
