"""Peak bytes of the fullest chip over the emptiest's (several chips
only): 1 is an even layout."""
from harness import monitor


def read(run):
    peaks = monitor.memory_peaks(run.devices)
    if len(peaks) < 2 or not min(peaks):
        return None
    return max(peaks) / min(peaks)
