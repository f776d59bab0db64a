"""peak_hbm_gb (GB, 1e9 bytes) - layer: device.
``memory_stats()["peak_bytes_in_use"]`` after the window, highest over the
chips used."""


def read(record):
    peak = record.get("memory_peak_bytes")
    return None if peak is None else peak / 1e9
