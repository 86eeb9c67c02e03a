"""Pure-jnp oracle for the streaming top-k MIPS kernel."""

import jax
import jax.numpy as jnp


def mips_topk_ref(V: jax.Array, q: jax.Array, k: int):
    """Exact top-k inner products: returns (idx int32 (k,), scores f32 (k,))."""
    scores = jnp.matmul(V.astype(jnp.float32), q.astype(jnp.float32),
                        precision="highest")
    top_s, top_i = jax.lax.top_k(scores, k)
    return top_i.astype(jnp.int32), top_s
