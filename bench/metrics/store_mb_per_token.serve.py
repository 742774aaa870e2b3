"""Store bytes read per output token of the window's waves, in MB
(10**6), from the store's own ledger (``IOStats``)."""


def read(record):
    if not record.get("window_waves"):
        return None
    return record["store_read_bytes"] / record["window_tokens"] / 1e6
