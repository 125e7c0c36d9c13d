"""The most bytes any chip had in use at a look taken while the traced
chunks ran (``bytes_in_use``, every 50 ms): what the job keeps on the
chip while it is measured. ``device.peak_hbm_bytes`` is the most it ever
held, and that is a transient of the upload."""


def read(run):
    held = max(run.facts.get("window_in_use_bytes") or [0])
    return float(held) if held else None
