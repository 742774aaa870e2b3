"""Weight passes (prefill groups plus decode steps, as ``ServingReport``
counts them) per output token of the window's waves."""


def read(record):
    if not record.get("window_waves"):
        return None
    return record["weight_passes"] / record["window_tokens"]
