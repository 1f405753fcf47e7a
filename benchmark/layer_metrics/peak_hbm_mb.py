"""device.memory_stats()["peak_bytes_in_use"] after the window, MB."""


def read(run):
    peak = run.setup.get("memory_peak_bytes")
    return peak / 1e6 if peak else None
