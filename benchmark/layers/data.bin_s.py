"""Seconds ``Dataset.construct()`` took for the training and validation
sets: binning on the host."""


def read(run):
    return run.phases.get("bin")
