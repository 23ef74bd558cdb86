"""Median host time of DecisionFastPath.submit (pad, stage, transfer,
dispatch) over the window's rounds. Layer: serving/fastpath."""
from benchlib import readers


def read(data):
    return readers.median_ms(data, "submit_s")
