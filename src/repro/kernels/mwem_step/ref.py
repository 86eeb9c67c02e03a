"""Pure-jnp oracle for the fused MWEM step (measure → MWU → renormalize).

`mwu_apply_ref` is THE multiplicative-weights update expression: the host
loop's `_mwu_step`, both fused scan cores, the sharded driver's model tail
and the Pallas megakernel all reduce to this one function, so the kernel
has a single integration seam and cross-driver bitwise parity cannot drift
(ISSUE 6 satellite: the `_mwu_update` alias and the raw `_mwu_step` partial
were two copies of this math).

Carried-density invariant the megakernel scan relies on: every update ends
with ``log_w -= max(log_w)``, so the carried log-weights have max exactly
0.0 and next step's ``softmax(log_w)`` reproduces the ``p_new`` emitted
here bit-for-bit (IEEE ``x - 0.0 == x``). That is what lets the scan carry
``p`` alongside ``log_w`` and skip the per-step softmax entirely.

Randomness stays outside this seam: the caller draws the Laplace
measurement noise from ``k_meas`` and passes the realized scalar in, so the
kernel body is deterministic and the PR 5 key-chain conformance holds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.faults import fault_site

UPDATE_RULES = ("paper", "signed", "hardt")


def mwu_apply_ref(log_w: jax.Array, p: jax.Array, q_row: jax.Array,
                  h: jax.Array, noise: jax.Array, *, rule: str,
                  eta: float) -> tuple[jax.Array, jax.Array]:
    """One MW update given the selected query row and realized noise.

    Args:
      log_w: (U,) carried log-weights (max-shifted: max == 0).
      p: (U,) carried density, ``softmax(log_w)`` of the input.
      q_row: (U,) the selected query row.
      h: (U,) true histogram.
      noise: scalar Laplace measurement noise (ignored for ``rule="paper"``,
        which takes no measurement).

    Returns ``(log_w', p')`` with ``max(log_w') == 0`` and
    ``p' == softmax(log_w')``.
    """
    if rule == "paper":
        lw = log_w - eta * q_row
    else:
        # f32 products summed along U — the megakernel's expression
        measured = jnp.sum(q_row * h) + noise
        est = jnp.sum(q_row * p)
        if rule == "signed":
            lw = log_w + eta * jnp.sign(measured - est) * q_row
        elif rule == "hardt":
            lw = log_w + q_row * (measured - est) / 2.0
        else:
            raise ValueError(f"unknown update rule {rule!r}")
    lw = lw - jnp.max(lw)  # drift control
    return lw, jax.nn.softmax(lw)


def mwem_step_ref(log_w: jax.Array, p: jax.Array, p_sum: jax.Array,
                  q_row: jax.Array, h: jax.Array, noise: jax.Array, *,
                  rule: str, eta: float
                  ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """XLA reference for the megakernel: MWU + renorm + output accumulation.

    Returns ``(log_w', p', p_sum + p')`` — exactly the state the fused scan
    carries per lane.
    """
    fault_site("kernel.mwem_step")
    lw, p_new = mwu_apply_ref(log_w, p, q_row, h, noise, rule=rule, eta=eta)
    return lw, p_new, p_sum + p_new
