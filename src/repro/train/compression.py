"""int8 error-feedback gradient compression for the cross-pod axis.

At 512+ chips the pod-to-pod (DCN/ICI-bridge) all-reduce is the scarcest
bandwidth. We compress the cross-pod gradient exchange to int8 with
per-tensor-block scales and an error-feedback buffer (the quantization
residual is added back into the next step's gradient), which preserves
convergence (Karimireddy et al. 2019) while cutting cross-pod bytes 4×.

The exchange itself is a ring all-reduce built from ``lax.ppermute``:
P−1 reduce-scatter hops + P−1 all-gather hops, each moving int8 chunks and
accumulating in f32 locally — int8 summation never overflows because
accumulation happens post-dequantization.

Intended use: inside ``shard_map`` over the "pod" mesh axis, with the
intra-pod reduction already done by the partitioner (psum over "data").
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

BLOCK = 2048


def _quantize(x: jax.Array):
    """Per-block symmetric int8 quantization. x: flat f32."""
    n = x.shape[0]
    pad = (-n) % BLOCK
    xp = jnp.pad(x, (0, pad)).reshape(-1, BLOCK)
    scale = jnp.max(jnp.abs(xp), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(xp / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32), n


def _dequantize(q, scale, n):
    return (q.astype(jnp.float32) * scale).reshape(-1)[:n]


def ring_allreduce_int8(x: jax.Array, axis_name: str) -> jax.Array:
    """Mean all-reduce of a flat f32 vector with int8 wire format.

    Must run inside shard_map/pmap over ``axis_name``.
    """
    P = jax.lax.axis_size(axis_name)
    if P == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    n = x.shape[0]
    pad = (-n) % P
    xp = jnp.pad(x, (0, pad)).reshape(P, -1)     # P chunks
    perm_fwd = [(i, (i + 1) % P) for i in range(P)]

    # reduce-scatter: after P−1 hops, chunk (idx+1) holds the full sum here
    acc = xp
    for step in range(P - 1):
        send_chunk = (idx - step) % P
        payload = jnp.take(acc, send_chunk, axis=0)
        q, s, m = _quantize(payload)
        q = jax.lax.ppermute(q, axis_name, perm_fwd)
        s = jax.lax.ppermute(s, axis_name, perm_fwd)
        recv_chunk = (idx - step - 1) % P
        recovered = _dequantize(q, s, m)
        acc = acc.at[recv_chunk].add(recovered.reshape(acc.shape[1:]))

    # all-gather: circulate the reduced chunks
    own = (idx + 1) % P
    out = jnp.zeros_like(acc)
    cur = jnp.take(acc, own, axis=0)
    out = out.at[own].set(cur)
    for step in range(P - 1):
        q, s, m = _quantize(cur)
        q = jax.lax.ppermute(q, axis_name, perm_fwd)
        s = jax.lax.ppermute(s, axis_name, perm_fwd)
        cur = _dequantize(q, s, m).reshape(acc.shape[1:])
        chunk_id = (own - step - 1) % P
        out = out.at[chunk_id].set(cur)

    return out.reshape(-1)[:n] / P


def ef_allreduce_grads(grads, opt_state, pod_axis: str):
    """Error-feedback int8 cross-pod gradient all-reduce.

    The error buffer lives in ``opt_state["ef_error"]`` (created lazily).
    Returns (new_grads, new_opt_state).
    """
    flat, treedef = jax.tree.flatten(grads)
    sizes = [x.size for x in flat]
    shapes = [x.shape for x in flat]
    vec = jnp.concatenate([x.reshape(-1).astype(jnp.float32) for x in flat])

    err = opt_state.get("ef_error")
    if err is None:
        err = jnp.zeros_like(vec)
    vec = vec + err

    # local quantization error becomes next step's feedback
    q, s, n = _quantize(vec)
    sent = _dequantize(q, s, n)
    new_err = vec - sent

    reduced = ring_allreduce_int8(sent, pod_axis)

    out, offset = [], 0
    for size, shape in zip(sizes, shapes):
        out.append(reduced[offset:offset + size].reshape(shape))
        offset += size
    new_opt_state = dict(opt_state)
    new_opt_state["ef_error"] = new_err
    return jax.tree.unflatten(treedef, out), new_opt_state
