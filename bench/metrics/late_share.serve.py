"""% of the window's rounds answered more than the traffic's
``late_after_s`` after they were due (late, not failed): how often a stall
of the host or the device holds a decision past a round."""


def read(data):
    if not data.get("rounds"):
        return None
    return 100.0 * data["late"] / data["rounds"]
