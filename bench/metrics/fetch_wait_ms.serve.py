"""Milliseconds the executor waited for weights at its fetches, per
weight pass (a prefill group or a decode step) of the window's waves."""


def read(record):
    if not record.get("window_waves"):
        return None
    return 1000.0 * record["fetch_wait_s"] / record["weight_passes"]
