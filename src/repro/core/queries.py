"""Linear-query workloads and LP instance generators (paper §5).

Everything is generated from explicit PRNG keys so data pipelines are
deterministic and shardable (any host can regenerate any piece).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.precision import dot as _dot


def gaussian_histogram(key: jax.Array, n: int, U: int, mean=None, std=None) -> jax.Array:
    """§5.1 dataset: n points from N(U/3, U/15) binned into [0, U)."""
    mean = U / 3.0 if mean is None else mean
    std = U / 15.0 if std is None else std
    pts = mean + std * jax.random.normal(key, (n,))
    idx = jnp.clip(jnp.round(pts).astype(jnp.int32), 0, U - 1)
    h = jnp.zeros((U,), jnp.float32).at[idx].add(1.0)
    return h / n


def random_binary_queries(key: jax.Array, m: int, U: int, mean=None, std=None) -> jax.Array:
    """§5.1 queries: binary vectors marking U/4 draws from N(U/2, U/5)."""
    mean = U / 2.0 if mean is None else mean
    std = U / 5.0 if std is None else std
    n_pts = max(U // 4, 1)
    pts = mean + std * jax.random.normal(key, (m, n_pts))
    idx = jnp.clip(jnp.round(pts).astype(jnp.int32), 0, U - 1)
    q = jnp.zeros((m, U), jnp.float32)
    rows = jnp.broadcast_to(jnp.arange(m)[:, None], idx.shape)
    return q.at[rows, idx].set(1.0)


def interval_queries(key: jax.Array, m: int, U: int, min_w: int = 1) -> jax.Array:
    """Random interval (range) queries — classic workload for histograms."""
    k1, k2 = jax.random.split(key)
    lo = jax.random.randint(k1, (m,), 0, U - min_w)
    width = jax.random.randint(k2, (m,), min_w, U // 2 + 1)
    hi = jnp.minimum(lo + width, U)
    pos = jnp.arange(U)[None, :]
    return ((pos >= lo[:, None]) & (pos < hi[:, None])).astype(jnp.float32)


def ngram_marginal_queries(key: jax.Array, m: int, U: int, arity: int = 64) -> jax.Array:
    """Random subset-marginal queries over a token domain (LM DP pipeline).

    Each row marks exactly ``arity`` *distinct* bins: indices are drawn
    without replacement per row (argsort of per-row uniforms — a random
    ``arity``-subset), so every row sums to ``arity``. The old
    ``randint``-with-replacement draw silently yielded rows with fewer
    distinct bins, skewing row norms and the EM utility scale.
    """
    if arity > U:
        raise ValueError(f"arity {arity} exceeds domain size {U}")
    u = jax.random.uniform(key, (m, U))
    idx = jnp.argsort(u, axis=1)[:, :arity]     # per-row random subset
    q = jnp.zeros((m, U), jnp.float32)
    rows = jnp.broadcast_to(jnp.arange(m)[:, None], idx.shape)
    return q.at[rows, idx].set(1.0)


def max_error(Q, h: jax.Array, p: jax.Array) -> jax.Array:
    """‖Q(p − h)‖_∞ — the utility objective (Eq. 1).

    ``Q`` is a dense (m, U) matrix or any `core.workload.Workload`:
    workloads answer through their own ``max_err`` (factored ones without
    densifying), and the dense array path below is byte-for-byte the
    pre-workload expression.
    """
    if hasattr(Q, "max_err"):
        return Q.max_err(h, p)
    return jnp.max(jnp.abs(_dot(Q, p - h)))


def random_feasible_lp(key: jax.Array, m: int, d: int, slack: float = 0.1):
    """§5.2 LP instance: A ~ N(0, I), x* ∈ Δ([d]), b = A x* + |δ| (feasible).

    Returns (A, b, x_star) as float32 arrays.
    """
    ka, kx, kd = jax.random.split(key, 3)
    A = jax.random.normal(ka, (m, d), jnp.float32)
    x_star = jax.random.dirichlet(kx, jnp.ones((d,), jnp.float32))
    delta = slack * jnp.abs(jax.random.normal(kd, (m,), jnp.float32))
    b = A @ x_star + delta
    return A, b, x_star


def random_packing_lp(key: jax.Array, m: int, d: int):
    """Positive (packing) LP for the constraint-private dual solver (§4.2).

    max c^T x  s.t.  A x ≤ b,  x ≥ 0  with A, b, c > 0.
    """
    ka, kb, kc = jax.random.split(key, 3)
    A = jax.random.uniform(ka, (m, d), jnp.float32, 0.1, 1.0)
    c = jax.random.uniform(kc, (d,), jnp.float32, 0.5, 1.5)
    b = jax.random.uniform(kb, (m,), jnp.float32, 0.5, 1.5)
    return A, b, c


def np_seed(key: jax.Array) -> int:
    """Derive a numpy seed from a JAX key (for offline index builds)."""
    return int(np.asarray(jax.random.key_data(key)).ravel()[-1] % (2**31 - 1))
