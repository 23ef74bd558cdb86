"""Plain reference of the multi-edge cluster's physics (paper §III, the
lane model of the scheduler's engine), for one cluster and one replay of
given assignments.

A request of round r (arrival in (r dt, (r+1) dt]) is dispatched at
T = (r+1) dt to edge e: its data is ready at T if e is its source, else at
T + ct * size * w[src, e] (eq 2). Each edge runs ``replicas`` lanes, FIFO by
ready time (ties in arrival order): a request starts at max(ready, the
earliest free lane), on the first such lane, and runs a * size + b
(at least 1e-6 s).

``replay`` also rebuilds the snapshot each round's decision saw: at T the
workload features are, per edge, the summed phi(size) of committed requests
waiting (ready <= T < start) over replicas (eq 1), of those in transfer
(ready > T) over replicas (eq 3), and the longest transfer of those in
transfer (eq 2).

``dtype`` sets the arithmetic of times: float32, the engine's stated
precision, for the reference (the lane order is defined on that clock; in
float64 a near-tie of two ready times can order differently and move a
finish by a whole service time), bfloat16 for its control.
"""
from __future__ import annotations

import numpy as np

MIN_RUNTIME = 1e-6


def replay(cl: dict, arr: dict, assign: np.ndarray, dt: float, ct: float,
           dtype=np.float32) -> dict:
    """Finish times (rounds, width) (inf where no request) and the snapshot
    of every round, for assignments ``assign`` (rounds, width)."""
    f = lambda x: np.asarray(x, dtype)  # noqa: E731
    rounds, width = arr["mask"].shape
    q = cl["w"].shape[0]
    mask = arr["mask"]
    rr, cc = np.nonzero(mask)                    # slot order: round, column
    e = assign[rr, cc].astype(np.int64)
    src = arr["src"][rr, cc].astype(np.int64)
    size = f(arr["size"][rr, cc])
    commit = f((rr + 1) * dt)
    delay = f(ct) * size * f(cl["w"][src, e])
    ready = np.where(e == src, commit, commit + delay).astype(dtype)
    rt = np.maximum(f(cl["a"][e]) * size + f(cl["b"][e]), f(MIN_RUNTIME))
    lanes = [np.zeros(int(cl["replicas"][k]), dtype) for k in range(q)]
    start = np.zeros(ready.size, dtype)
    finish = np.zeros(ready.size, dtype)
    for i in sorted(range(ready.size), key=lambda i: (float(ready[i]), i)):
        ln = lanes[e[i]]
        j = int(np.argmin(ln))
        start[i] = max(ready[i], ln[j])
        finish[i] = start[i] + rt[i]
        ln[j] = finish[i]
    fin = np.full((rounds, width), np.inf)
    fin[rr, cc] = finish.astype(np.float64)

    phi = np.stack([cl["a"], cl["b"]], -1).astype(np.float32)
    snaps = []
    comp = (cl["a"][e] * arr["size"][rr, cc] + cl["b"][e]).astype(np.float64)
    trans = (ct * arr["size"][rr, cc] * cl["w"][src, e]).astype(np.float64)
    for r in range(rounds):
        t = (r + 1) * dt
        prior = rr < r
        waiting = prior & (ready <= t) & (start > t)
        moving = prior & (ready > t)
        c_le = np.bincount(e[waiting], comp[waiting], q) / cl["replicas"]
        c_in = np.bincount(e[moving], comp[moving], q) / cl["replicas"]
        t_in = np.zeros(q)
        np.maximum.at(t_in, e[moving], trans[moving])
        m = mask[r]
        snaps.append({
            "edge_coords": cl["coords"].astype(np.float32),
            "phi": phi,
            "replicas": cl["replicas"].astype(np.float32),
            "workload": np.stack([c_le, c_in, t_in], -1).astype(np.float32),
            "w": cl["w"].astype(np.float32),
            "ct": np.float32(ct),
            "req_src": arr["src"][r].astype(np.int32),
            "req_size": np.where(m, arr["size"][r], 0).astype(np.float32),
            "edge_mask": np.ones(q, bool),
            "req_mask": m.copy(),
        })
    return {"finish": fin, "snapshots": snaps}
