"""Share of the traced window in which no operation ran on the device,
in percent, in a serving cell."""


def read(record):
    trace = record.get("trace")
    if trace is None or "window_waves" not in record:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
