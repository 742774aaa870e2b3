"""Store bytes read and written per window step, in GB (10**9), from the
store's own ledger (``IOStats``)."""


def read(record):
    steps = record.get("window_steps")
    if not steps:
        return None
    moved = record["store_read_bytes"] + record["store_written_bytes"]
    return moved / len(steps) / 1e9
