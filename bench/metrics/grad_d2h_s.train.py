"""Seconds per window step that the gradient writer spent copying
gradients to the host and into the flat buffer (``grad_d2h_s`` of
``OffloadSession.train_step``; none where the program has no such
counter)."""


def read(record):
    steps = record.get("window_steps")
    if not steps or "grad_d2h_s" not in steps[0]:
        return None
    return sum(m["grad_d2h_s"] for m in steps) / len(steps)
