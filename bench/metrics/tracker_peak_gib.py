"""The program's own peak of tracked host allocations
(``MemoryTracker.peak_allocated``) over the whole run, in GiB.  It cannot
see untracked memory: numpy temporaries, the runtime, Python."""


def read(record):
    peak = record.get("tracker_peak_bytes")
    return None if peak is None else peak / 2**30
