"""The whole training step's share of the chip's peak: model FLOPs of the
window's tokens (``bench/flops.py``, no recomputation) over the window's
length, over the peak of ``bench/peaks.json`` times the chips, in
percent."""


def read(record):
    if "window_steps" not in record:
        return None
    rate = record["model_flops"] / record["window_s"]
    return 100.0 * rate / (record["peak_flops_per_s"] * record["chips"])
