"""% of the traced rollout window in which the device ran no operation.
Layer: the device."""
from benchlib import readers


def read(data):
    return readers.idle_share(data)
