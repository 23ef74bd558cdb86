"""Median host time of DecisionFastPath.result (wait for the device, fetch,
strip padding) over the window's rounds. Layer: serving/fastpath."""
from benchlib import readers


def read(data):
    return readers.median_ms(data, "result_s")
