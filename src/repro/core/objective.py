"""The paper's scheduling objective — eqs (4)-(11) / reward eqs (18)-(19).

Two implementations, cross-validated by property tests:

* :func:`makespan` — batched jnp, differentiable-through-none (pure eval),
  used as the RL reward and as the objective the ILP/solvers optimize.
* :func:`makespan_np` — scalar numpy mirror used by the exact solvers and
  heuristics (cheap incremental recomputation per edge).

Conventions: assignment ``x`` maps each request to an edge index;
``T_q = max(kappa_q, mu_q) + eta_q`` (eq 9); objective = max_q T_q (eq 4).
Note eq (7)'s transmission max over z includes local requests with
w[src,src] = 0, so masking src != q is equivalent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

NEG = -1e9
#: The per-edge sums below are one-hot contractions that XLA lowers to dots.
#: They are bookkeeping, not a network layer: on the TPU the default f32 dot
#: precision (one bf16 pass) would round the per-edge loads mu and eta to
#: ~3 significant digits, so they run at full f32 precision (a no-op on the
#: CPU).
_EXACT = jax.lax.Precision.HIGHEST


def phi_eval(phi, sizes):
    """phi: (..., Q, 2); sizes: (..., Z) -> (..., Z, Q) computation times."""
    return phi[..., None, :, 0] * sizes[..., :, None] + phi[..., None, :, 1]


def per_edge_times(inst, assign):
    """All per-edge terms for one assignment. assign: (..., Z) int32.

    Returns dict with mu, eta, kappa, T each (..., Q).
    """
    q_pad = inst["phi"].shape[-2]
    sizes = inst["req_size"]
    src = inst["req_src"]
    rmask = inst["req_mask"].astype(jnp.float32)

    onehot = jax.nn.one_hot(assign, q_pad, dtype=jnp.float32) * rmask[..., None]
    local = (assign == src).astype(jnp.float32)  # (..., Z)

    comp = phi_eval(inst["phi"], sizes)  # (..., Z, Q)
    # eq (5): locally-executed new work + local backlog
    mu = (
        jnp.einsum("...zq,...zq->...q", onehot * local[..., None], comp,
                   precision=_EXACT)
        / inst["replicas"]
        + inst["workload"][..., 0]
    )
    # eq (6): transferred-in new work + transferred-in backlog
    eta = (
        jnp.einsum("...zq,...zq->...q", onehot * (1.0 - local[..., None]), comp,
                   precision=_EXACT)
        / inst["replicas"]
        + inst["workload"][..., 1]
    )
    # eq (7): slowest incoming transfer among newly transferred requests
    w_src = jnp.take_along_axis(
        inst["w"], src[..., :, None].astype(jnp.int32), axis=-2
    )  # (..., Z, Q) distance from each request's source to every edge
    trans = sizes[..., :, None] * w_src * onehot  # zero where not assigned
    v = jnp.max(trans, axis=-2)  # (..., Q)
    # eq (8): include still-in-flight backlog transfers
    kappa = jnp.maximum(inst["ct"][..., None] * v, inst["workload"][..., 2])
    # eq (9)
    T = jnp.maximum(kappa, mu) + eta
    return {"mu": mu, "eta": eta, "kappa": kappa, "T": T}


def makespan(inst, assign) -> jax.Array:
    """Objective eq (4) / reward L(pi) = -u_hat of eq (19): max_q T_q over
    real edges. assign: (..., Z). Returns (...) f32."""
    T = per_edge_times(inst, assign)["T"]
    T = jnp.where(inst["edge_mask"], T, NEG)
    return jnp.max(T, axis=-1)


def makespan_batch_samples(inst, assigns) -> jax.Array:
    """inst: single instance (no batch axis); assigns: (S, Z). -> (S,)"""
    return jax.vmap(lambda a: makespan(inst, a))(assigns)


# ---------------------------------------------------------------------------
# numpy mirror (scalar, for solvers)
# ---------------------------------------------------------------------------


def per_edge_times_np(inst, assign: np.ndarray) -> dict:
    phi = np.asarray(inst["phi"])
    q_pad = phi.shape[0]
    sizes = np.asarray(inst["req_size"])
    src = np.asarray(inst["req_src"])
    rmask = np.asarray(inst["req_mask"])
    w = np.asarray(inst["w"])
    wl = np.asarray(inst["workload"])
    reps = np.asarray(inst["replicas"])
    ct = float(inst["ct"])

    mu = wl[:, 0].copy()
    eta = wl[:, 1].copy()
    v = np.zeros(q_pad, np.float64)
    for z in np.nonzero(rmask)[0]:
        q = int(assign[z])
        t = float(phi[q, 0] * sizes[z] + phi[q, 1])
        if q == src[z]:
            mu[q] += t / reps[q]
        else:
            eta[q] += t / reps[q]
            v[q] = max(v[q], float(sizes[z] * w[src[z], q]))
    kappa = np.maximum(ct * v, wl[:, 2])
    T = np.maximum(kappa, mu) + eta
    return {"mu": mu, "eta": eta, "kappa": kappa, "T": T}


def makespan_np(inst, assign: np.ndarray) -> float:
    T = per_edge_times_np(inst, assign)["T"]
    emask = np.asarray(inst["edge_mask"])
    return float(np.max(np.where(emask, T, -np.inf)))
