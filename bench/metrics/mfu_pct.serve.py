"""Serving's share of the chip's peak: model FLOPs of the window's
prefilled and decoded tokens (``bench/flops.py``) over the window's
length, over the peak of ``bench/peaks.json`` times the chips, in
percent."""


def read(record):
    if "window_waves" not in record:
        return None
    rate = record["model_flops"] / record["window_s"]
    return 100.0 * rate / (record["peak_flops_per_s"] * record["chips"])
