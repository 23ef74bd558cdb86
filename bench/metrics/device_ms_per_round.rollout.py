"""Device time of the rollout program per batched round (all instances of
a call advance one round together), over the calls traced: the engine
(advance lane scan, stable_order, commit, round_instance) and the policy
deciding inside it. The TPU trace's operations carry no name stack, so the
split between the two waits on spans inside the program. Layer:
serving/engine."""
from benchlib import readers


def read(data):
    busy = readers.busy_seconds(data)
    if busy is None or not data.get("batched_rounds"):
        return None
    return busy / data["batched_rounds"] * 1e3
