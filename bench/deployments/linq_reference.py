"""Plain reference of the linear-query release (MWEM, paper Alg. 1/2).

Imports nothing of the program. It holds the semantics a release must
satisfy, written out plainly:

* the seed's key chain: ``key → (key, k_sel, k_meas)`` per iteration, the
  Laplace measurement noise ``lap_scale · Laplace(k_meas)`` with
  ``lap_scale = (1/n) / (ε₀/2)`` and ``ε₀ = ε / √(T ln(1/δ))``;
* the multiplicative-weights step under the "hardt" rule, given the
  selected query ``q``: ``log w += q · (⟨q, h⟩ + noise − ⟨q, p⟩) / 2``,
  renormalised; the release is the mean density over the T steps;
* the release's error ``max_j |⟨q_j, p̂ − h⟩|`` and a read's answer
  ``⟨q, p̂⟩``, recomputed in float64.

`replay` re-runs the multiplicative-weights steps on the program's own
selections (which are random draws the reference cannot repeat), so the
release is checked step for step, and keeps the states at a few sampled
steps. At those states `scores64` scores every row in float64: how far
below the best candidate each selection lies (`selection_gaps`), how
far the probe's returned scores lie from float64 (`probe_gaps`), and how
far a candidate the probe left out scores above its returned set
(`topk_boundary_gaps`).
`mechanism_low`, `probe_low` and `answer_low` are the whole plain
release, the exhaustive probe and a read one precision step below the
configuration's (`CONTROLS`): the controls, which `bench/control.py`
puts in the program's place.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

F32_REL = 2.0 ** -18   # f32 sums of ≤ 2¹⁴ terms, in any order, with margin


def lap_scale(eps: float, delta: float, T: int, n_records: int) -> float:
    eps0 = eps / math.sqrt(T * math.log(1.0 / delta))
    return (1.0 / n_records) / (eps0 * 0.5)


def em_scale(eps: float, delta: float, T: int, n_records: int) -> float:
    """The exponential mechanism's factor ε_em / (2Δu), Δu = 1/n."""
    eps0 = eps / math.sqrt(T * math.log(1.0 / delta))
    return float(eps0 * 0.5 / (2.0 / n_records))


@partial(jax.jit, static_argnames=("T",))
def _chains(keys, T: int):
    """(R, T) selection keys and measurement keys of each lane's chain."""
    def one(key):
        def body(k, _):
            k, k_sel, k_meas = jax.random.split(k, 3)
            return k, (k_sel, k_meas)
        return jax.lax.scan(body, key, None, length=T)[1]
    return jax.vmap(one)(keys)


def noise(seeds, T: int, scale: float) -> jax.Array:
    """(R, T) realised measurement noise for releases keyed by ``seeds``."""
    keys = jnp.stack([jax.random.PRNGKey(int(s)) for s in seeds])
    _, k_meas = _chains(keys, T)
    return jax.vmap(jax.vmap(lambda k: scale * jax.random.laplace(k)))(k_meas)


def _mwu(lw, p, q, h, nz, dtype):
    """One "hardt" step in ``dtype`` → (log w', p')."""
    lw, p, q, h = (x.astype(dtype) for x in (lw, p, q, h))
    measured = jnp.sum(q * h, axis=-1, keepdims=True) + nz.astype(dtype)[:, None]
    est = jnp.sum(q * p, axis=-1, keepdims=True)
    lw = lw + q * (measured - est) / 2
    lw = lw - jnp.max(lw, axis=-1, keepdims=True)
    return lw, jax.nn.softmax(lw, axis=-1)


@jax.jit
def _replay(Q, H, sel, nz, at):
    """p̂ and the states p_t before the steps ``at`` (S,), in f32."""
    R, U = H.shape
    T = sel.shape[1]

    def body(carry, xs):
        lw, p, ps, seen = carry
        t, s, z = xs
        seen = jnp.where((at == t)[:, None, None], p[None], seen)
        lw, p = _mwu(lw, p, Q[s], H, z, jnp.float32)
        return (lw, p, ps + p, seen), None

    lw0 = jnp.zeros((R, U), jnp.float32)
    p0 = jax.nn.softmax(lw0, axis=-1)
    seen0 = jnp.zeros((at.shape[0], R, U), jnp.float32)
    (_, _, ps, seen), _ = jax.lax.scan(
        body, (lw0, p0, jnp.zeros((R, U), jnp.float32), seen0),
        (jnp.arange(T), sel.T, nz.T))
    return ps / T, seen


def replay(Q, H, selected, nz, at, block: int = 32):
    """Each release's p̂ from its selections and noise, and its states p_t
    before the steps ``at``, in f32 on the device: ((R, U), (R, S, U)).
    Blocks of ``block`` releases, padded, so one program serves every
    run."""
    H, selected, nz = (np.asarray(x) for x in (H, selected, nz))
    at = jnp.asarray(np.asarray(at, np.int32))
    P, seen = [], []
    for i in range(0, len(H), block):
        n = min(block, len(H) - i)
        pad = lambda x: np.concatenate(  # noqa: E731
            [x[i:i + n], np.repeat(x[i:i + 1], block - n, axis=0)])
        p, s = _replay(Q, jnp.asarray(pad(H)), jnp.asarray(pad(selected)),
                       jnp.asarray(pad(nz)), at)
        P.append(np.asarray(p)[:n])
        seen.append(np.asarray(s).swapaxes(0, 1)[:n])
    U = H.shape[-1]
    if not P:
        return np.zeros((0, U), np.float32), np.zeros((0, len(at), U))
    return np.concatenate(P), np.concatenate(seen)


def scores64(Q8: np.ndarray, V: np.ndarray):
    """(⟨q_j, v⟩, ⟨q_j, |v|⟩) in float64 for every base row j and vector
    v: two (m, n) arrays. The second is the sum of magnitudes an f32
    score's rounding scales with (|q| = q for 0/1 rows)."""
    V64 = np.asarray(V, np.float64).T                       # (U, n)
    S = np.zeros((len(Q8), V64.shape[1]))
    M = np.zeros_like(S)
    for i in range(0, len(Q8), 2048):
        rows = Q8[i:i + 2048].astype(np.float64)
        S[i:i + 2048], M[i:i + 2048] = rows @ V64, rows @ np.abs(V64)
    return S, M


def selection_gaps(S: np.ndarray, selected: np.ndarray,
                   scale: float) -> np.ndarray:
    """How far below the best candidate each selection scores, in the
    exponential mechanism's units (nats): scale · (max_j |s_j| − |s_sel|)
    per column of ``S``; the candidates are the rows and their
    complements, so a row scores |s_j| at its better sign."""
    A = np.abs(S)
    picked = A[np.asarray(selected), np.arange(S.shape[1])]
    return scale * (A.max(axis=0) - picked)


def probe_gaps(S: np.ndarray, M: np.ndarray, ids: np.ndarray,
               raw: np.ndarray) -> np.ndarray:
    """Each returned candidate's score against float64, in units of the
    f32 bound F32_REL · ⟨q, |v|⟩: (n, k). ``ids`` are augmented (j < m is
    +⟨q_j, v⟩, j ≥ m is −⟨q_{j−m}, v⟩); an id out of range reads inf."""
    m = S.shape[0]
    ids, raw = np.asarray(ids, np.int64), np.asarray(raw, np.float64)
    ok = (ids >= 0) & (ids < 2 * m)
    base = np.where(ok, ids % m, 0)
    col = np.arange(S.shape[1])[:, None]
    want = np.where(ids < m, 1.0, -1.0) * S[base, col]
    gap = np.abs(raw - want) / (F32_REL * M[base, col] + 1e-30)
    return np.where(ok, gap, np.inf)


def topk_boundary_gaps(S: np.ndarray, M: np.ndarray,
                       ids: np.ndarray) -> np.ndarray:
    """How far the best candidate left out of each returned set scores
    above the set's worst, in units of the two rows' f32 bound; 0 where
    none left out scores higher: (n,). The lazy exponential mechanism is
    exact only when the set is the top-k (approximation constant c = 0);
    an exact top-k of f32 scores reads at most about two scores' rounding."""
    m = S.shape[0]
    out = np.zeros(S.shape[1])
    for c, row in enumerate(np.asarray(ids, np.int64)):
        aug = np.concatenate([S[:, c], -S[:, c]])
        mag = np.concatenate([M[:, c], M[:, c]])
        inside = np.zeros(2 * m, bool)
        inside[row[(row >= 0) & (row < 2 * m)]] = True
        worst = np.argmin(np.where(inside, aug, np.inf))
        best_out = np.argmax(np.where(inside, -np.inf, aug))
        gap = aug[best_out] - aug[worst]
        out[c] = max(gap, 0.0) / (F32_REL * max(mag[best_out], mag[worst])
                                  + 1e-30)
    return out


def topk_recall(S: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """Share of the float64 top-k candidates (rows and complements) among
    the k returned ids, per vector: (n,)."""
    m, k = S.shape[0], ids.shape[1]
    aug = np.concatenate([S, -S]).T                          # (n, 2m)
    best = np.argpartition(-aug, k - 1, axis=1)[:, :k]
    return np.asarray([len(np.intersect1d(b, i)) / k
                       for b, i in zip(best, np.asarray(ids))])


def release_errors64(Q8: np.ndarray, P: np.ndarray, H: np.ndarray):
    """(error, scale) per release in float64: ``max_j |⟨q_j, p̂ − h⟩|`` and
    ``max_j ⟨q_j, |p̂ − h|⟩``, the sum of magnitudes an f32 error scales
    with."""
    D = (np.asarray(P, np.float64) - np.asarray(H, np.float64)).T   # (U, R)
    err = np.zeros(D.shape[1])
    scale = np.zeros(D.shape[1])
    for i in range(0, len(Q8), 2048):
        rows = Q8[i:i + 2048].astype(np.float64)
        err = np.maximum(err, np.max(np.abs(rows @ D), axis=0))
        scale = np.maximum(scale, np.max(rows @ np.abs(D), axis=0))
    return err, scale


def answers64(Q8: np.ndarray, rows, release_ids, p_by_id) -> np.ndarray:
    """⟨q_row, p̂⟩ in float64 for each read (row, release answered from)."""
    rows, release_ids = np.asarray(rows), np.asarray(release_ids)
    out = np.zeros(len(rows))
    for rid in np.unique(release_ids):
        at = np.nonzero(release_ids == rid)[0]
        out[at] = Q8[rows[at]].astype(np.float64) @ np.asarray(
            p_by_id[int(rid)], np.float64)
    return out


# ------------------------------------------------------ the whole release
@partial(jax.jit, static_argnames=("T", "scale", "lap", "dtype", "precision"))
def _mechanism(Q, H, keys, T: int, scale: float, lap: float, dtype,
               precision):
    R, U = H.shape
    k_sel, k_meas = _chains(keys, T)

    def body(carry, xs):
        lw, p, ps = carry
        ks, km = xs
        v = (H.astype(dtype) - p.astype(dtype)).astype(jnp.float32)
        s = jnp.dot(v, Q.T, precision=precision)               # (R, m)
        aug = jnp.concatenate([s, -s], axis=-1) * scale
        g = jax.vmap(lambda k: jax.random.gumbel(k, aug.shape[1:]))(ks)
        sel = jnp.argmax(aug + g, axis=-1) % Q.shape[0]
        nz = jax.vmap(lambda k: lap * jax.random.laplace(k))(km)
        lw, p = _mwu(lw, p, Q[sel], H, nz, dtype)
        return (lw, p, ps + p), sel

    lw0 = jnp.zeros((R, U), dtype)
    p0 = jax.nn.softmax(lw0, axis=-1)
    (_, _, ps), sel = jax.lax.scan(
        body, (lw0, p0, jnp.zeros((R, U), dtype)), (k_sel.swapaxes(0, 1),
                                                   k_meas.swapaxes(0, 1)))
    p_hat = ps.astype(jnp.float32) / T
    d = (p_hat.astype(dtype) - H.astype(dtype)).astype(jnp.float32)
    err = jnp.max(jnp.abs(jnp.dot(d, Q.T, precision=precision)), axis=-1)
    return p_hat, err, sel.T


# The controls: the reference one precision step below the configuration's
# (f32 elementwise state, products at HIGHEST). "low" steps both down
# (bf16 state and arithmetic, HIGH products); "high" and "default" step
# only the products down, to three bf16 passes or one.
CONTROLS = {
    "low": (jnp.bfloat16, jax.lax.Precision.HIGH),
    "high": (jnp.float32, jax.lax.Precision.HIGH),
    "default": (jnp.float32, jax.lax.Precision.DEFAULT),
}


def mechanism_low(Q, H, keys, *, T: int, eps: float, delta: float,
                  n_records: int, control: str = "low"):
    """The plain release of each lane keyed by ``keys`` at the precision
    of ``CONTROLS[control]``: (p̂ (R, U), error (R,), selected (R, T))."""
    dtype, precision = CONTROLS[control]
    return _mechanism(Q, jnp.asarray(H, jnp.float32), keys, T=T,
                      scale=em_scale(eps, delta, T, n_records),
                      lap=lap_scale(eps, delta, T, n_records),
                      dtype=dtype, precision=precision)


@partial(jax.jit, static_argnames=("k", "dtype", "precision"))
def _probe_low(Q, V, k: int, dtype, precision):
    v = V.astype(dtype).astype(jnp.float32)
    s = jnp.dot(v, Q.T, precision=precision)                 # (n, m)
    vals, ids = jax.lax.top_k(jnp.concatenate([s, -s], axis=-1), k)
    return ids, vals


def probe_low(Q, V, k: int, control: str = "low"):
    """The exhaustive top-k of the rows and their complements for each
    vector of ``V`` at the precision of ``CONTROLS[control]``:
    ((n, k) augmented ids, (n, k) scores)."""
    dtype, precision = CONTROLS[control]
    ids, vals = _probe_low(Q, jnp.asarray(V, jnp.float32), k=k, dtype=dtype,
                           precision=precision)
    return np.asarray(ids), np.asarray(vals)


def _bf16(x):
    return np.asarray(x, np.float32).astype(jnp.bfloat16).astype(np.float32)


def answer_low(q, p_hat, control: str = "low") -> float:
    """A read at the precision of ``CONTROLS[control]``, as the chip's
    passes compute it: the 0/1 row is exact in bf16, the release is
    rounded to bf16 once (one pass) or split into two bf16 parts (three
    passes), and the products are summed in f32."""
    dtype, precision = CONTROLS[control]
    p = np.asarray(p_hat, np.float32)
    if dtype == jnp.bfloat16 or precision == jax.lax.Precision.DEFAULT:
        p = _bf16(p)
    else:
        hi = _bf16(p)
        p = hi + _bf16(p - hi)
    return float(np.asarray(q, np.float32) @ p)
