"""Device-busy time per served decision (ms): the union of the device's
operations over the traced window, over the decisions answered in it.
Layer: core/policy + core/inference."""
from benchlib import readers


def read(data):
    busy = readers.busy_seconds(data)
    n = len(data.get("calls", ()))
    if busy is None or n == 0:
        return None
    return busy / n * 1e3
