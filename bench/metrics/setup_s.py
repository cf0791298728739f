"""Seconds from the start of the run to its first window request: weights,
DesignFlow, calibration, compiles (or compile-cache loads) and warm-up."""


def read(rec):
    return rec["setup_s"]
