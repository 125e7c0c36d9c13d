"""``peak_bytes_in_use`` of the fullest chip after the run."""
from harness import monitor


def read(run):
    peak = max(monitor.memory_peaks(run.devices))
    return float(peak) if peak else None
