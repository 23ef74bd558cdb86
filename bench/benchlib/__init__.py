"""The benchmark's yardstick: traffic generation, weights, the plain
reference, work counts, the peak table and the trace reduction.

Nothing here imports the scheduler (``repro``): these are the measures the
scheduler is held to, so a change to the program cannot move them.
"""
