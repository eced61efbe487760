"""Peak device memory as the window closed: `memory_stats()
["peak_bytes_in_use"]` of the fullest device.  args: `scale` (1e-9 for GB)."""


def read(*, cell, run, trace, scale: float = 1.0):
    return run.memory_peak_bytes * scale if run.memory_peak_bytes else None
