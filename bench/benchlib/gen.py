"""Traffic generation from the seed: scheduling snapshots and arrival
schedules. A copy of the paper's instance law (§V.A) as the scheduler's
``core/instances.py`` draws it, vectorized, so later changes to the program
cannot move the inputs.

Instance layout (the scheduler's snapshot dict, unpadded):
    edge_coords (q,2) phi (q,2) replicas (q,) workload (q,3) w (q,q) ct ()
    req_src (z,) req_size (z,) edge_mask (q,) req_mask (z,)
"""
from __future__ import annotations

import numpy as np


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    """A numpy generator for (seed, salt...); any non-negative seed."""
    return np.random.default_rng([int(seed), *salt])


def snapshot(rng: np.random.Generator, q: int, z: int, law: dict) -> dict:
    """One scheduling round of q edges and z requests.

    ``law``: phi_low/phi_high (phi coefficients ~ U), replicas_high
    (zeta ~ U{1..}), backlog_high (|Q^le|, |Q^in| ~ U{0..backlog_high-1}),
    ct. Sizes are U(0, 1), sources uniform, backlog senders uniform over the
    other edges. Workload features follow eqs (1)-(3)."""
    coords = rng.uniform(0.0, 1.0, (q, 2)).astype(np.float32)
    phi = rng.uniform(law["phi_low"], law["phi_high"], (q, 2)).astype(np.float32)
    replicas = rng.integers(1, law["replicas_high"] + 1, q).astype(np.float32)
    w = np.linalg.norm(coords[:, None] - coords[None], axis=-1).astype(np.float32)
    np.fill_diagonal(w, 0.0)
    nb = law["backlog_high"]
    n_le = rng.integers(0, nb, q)
    n_in = rng.integers(0, nb, q)
    cols = np.arange(nb)[None, :]
    s_le = np.where(cols < n_le[:, None], rng.uniform(0, 1, (q, nb)), 0.0)
    s_in = np.where(cols < n_in[:, None], rng.uniform(0, 1, (q, nb)), 0.0)
    # backlog senders: uniform over the other q-1 edges
    src = rng.integers(0, max(q - 1, 1), (q, nb))
    src = src + (src >= np.arange(q)[:, None])
    a, b = phi[:, 0].astype(np.float64), phi[:, 1].astype(np.float64)
    c_le = (a * s_le.sum(1) + b * n_le) / replicas                # eq (1)
    c_in = (a * s_in.sum(1) + b * n_in) / replicas                # eq (3)
    trans = law["ct"] * s_in * w[np.minimum(src, q - 1), np.arange(q)[:, None]]
    t_in = np.where(cols < n_in[:, None], trans, 0.0).max(1)      # eq (2)
    return {
        "edge_coords": coords,
        "phi": phi,
        "replicas": replicas,
        "workload": np.stack([c_le, c_in, t_in], -1).astype(np.float32),
        "w": w,
        "ct": np.float32(law["ct"]),
        "req_src": rng.integers(0, q, z).astype(np.int32),
        "req_size": rng.uniform(0.0, 1.0, z).astype(np.float32),
        "edge_mask": np.ones(q, bool),
        "req_mask": np.ones(z, bool),
    }


def _stratified(rng: np.random.Generator, n: int) -> np.ndarray:
    """n points (i + 1/2)/n of (0, 1), in an order drawn from ``rng``: every
    seed gets the same set of quantiles, shuffled."""
    return rng.permutation((np.arange(n) + 0.5) / n)


def snapshot_pool(seed: int, n: int, q: int, z_low: int, z_high: int,
                  law: dict) -> list[dict]:
    """``n`` snapshots of q edges and z ~ U{z_low..z_high} requests each;
    the z of the pool are the law's quantiles, so every seed's pool holds
    the same set of sizes."""
    rng = rng_for(seed, 0x5E)
    zs = z_low + np.floor(_stratified(rng, n) * (z_high - z_low + 1))
    return [snapshot(rng, q, int(z), law) for z in zs.astype(int)]


def pool_order(seed: int, pool: int, rounds: int) -> np.ndarray:
    """Which pool snapshot each of ``rounds`` rounds serves: the pool in
    seeded shuffles, each snapshot once per pass."""
    rng = rng_for(seed, 0x0D)
    passes = -(-rounds // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(passes)])[:rounds]


def poisson_schedule(seed: int, rate: float, seconds: float) -> np.ndarray:
    """Due times (s from the window start) of a Poisson stream at ``rate``
    per second over ``seconds``: round(rate * seconds) exponential gaps,
    taken at the law's quantiles and shuffled by the seed, so every seed
    offers the same load in another order."""
    n = max(int(round(rate * seconds)), 1)
    gaps = -np.log1p(-_stratified(rng_for(seed, 0xA1), n)) / rate
    return np.cumsum(gaps) * (seconds / (n / rate)) * (1 - 0.5 / n)


def pad_to(inst: dict, q_pad: int, z_pad: int) -> dict:
    """Zero-pad an instance to (q_pad, z_pad); masks pad with False."""
    q, z = inst["edge_mask"].shape[0], inst["req_mask"].shape[0]
    out = {}
    for k, v in inst.items():
        v = np.asarray(v)
        if k in ("edge_coords", "phi", "replicas", "workload", "edge_mask"):
            v = np.pad(v, ((0, q_pad - q),) + ((0, 0),) * (v.ndim - 1))
        elif k == "w":
            v = np.pad(v, ((0, q_pad - q), (0, q_pad - q)))
        elif k in ("req_src", "req_size", "req_mask"):
            v = np.pad(v, (0, z_pad - z))
        out[k] = v
    return out


def stack(insts: list[dict]) -> dict:
    return {k: np.stack([i[k] for i in insts]) for k in insts[0]}


def cluster(rng: np.random.Generator, q: int, law: dict) -> dict:
    """One engine cluster: edge coordinates U(0,1)^2, distances, hidden
    service lines phi(x) = a x + b with a ~ U(a_low, a_high),
    b ~ U(0, b_high), replica counts U{1..replicas_high}."""
    coords = rng.uniform(0.0, 1.0, (q, 2))
    return {
        "coords": coords,
        "w": np.linalg.norm(coords[:, None] - coords[None], axis=-1),
        "a": rng.uniform(law["a_low"], law["a_high"], q),
        "b": rng.uniform(0.0, law["b_high"], q),
        "replicas": rng.integers(1, law["replicas_high"] + 1, q),
    }


def round_arrivals(rng: np.random.Generator, q: int, rounds: int, dt: float,
                   rate: float, width: int) -> dict:
    """A Poisson stream of ``rate`` requests/s over (0, rounds*dt], sources
    uniform over q edges, sizes U(0,1), packed into (rounds, width) rows by
    the round window (r dt, (r+1) dt] each arrival falls in, in time order.
    Arrivals past ``width`` in a round are dropped and counted."""
    horizon = rounds * dt
    n = rng.poisson(rate * horizon)
    t = np.sort(rng.uniform(0.0, horizon, n))
    t = t[t > 0]
    src = rng.integers(0, q, t.size)
    size = rng.uniform(0.0, 1.0, t.size)
    row = np.clip(np.ceil(t / dt).astype(np.int64) - 1, 0, rounds - 1)
    out = {"t": np.zeros((rounds, width), np.float32),
           "src": np.zeros((rounds, width), np.int32),
           "size": np.zeros((rounds, width), np.float32),
           "mask": np.zeros((rounds, width), bool)}
    dropped = 0
    for r in range(rounds):
        idx = np.flatnonzero(row == r)
        dropped += max(idx.size - width, 0)
        idx = idx[:width]
        k = idx.size
        out["t"][r, :k] = t[idx]
        out["src"][r, :k] = src[idx]
        out["size"][r, :k] = size[idx]
        out["mask"][r, :k] = True
    out["dropped"] = dropped
    return out
