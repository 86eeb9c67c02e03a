"""f32-precision products for every score and answer the mechanisms use.

On a TPU, an f32 matmul at default precision rounds its operands to
bf16, about 2⁻⁹ relative error. The exponential mechanism's scores
⟨q, h − p̂⟩ carry sensitivity 1/n and its analysis assumes them exact,
and released answers ⟨q, p̂⟩ are the product users read, so every such
product asks for f32 precision explicitly. On CPU the flag changes
nothing.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def dot(a, b):
    """``a @ b`` at f32 precision."""
    return jnp.matmul(a, b, precision=HIGHEST)
