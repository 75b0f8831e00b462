"""Peak device memory as the result line reports it."""


def memory_peak_bytes(devices) -> int:
    """Peak on the fullest chip. XLA reserves a program's temporaries as
    one block that ``peak_bytes_in_use`` leaves out (PR 21: 2.78 GiB read
    for a 14.7 GiB step), so where the backend reports the reservation
    it is added to the live bytes."""
    peak = 0
    for d in devices:
        m = d.memory_stats() or {}
        here = m.get("peak_bytes_in_use", 0)
        if "peak_bytes_reserved" in m:
            here = max(here, m["peak_bytes_reserved"] + m.get("bytes_in_use", 0))
        peak = max(peak, here)
    return peak
