"""MLP variants + top-k MoE with sort-based capacity dispatch.

MoE (fixed shapes): token copies are sorted by expert id, placed into an
(E, C, d) capacity buffer by scatter, run through per-expert GEMMs, and
combined back with router weights. Overflowing tokens beyond capacity C
are dropped (standard Switch-style), C = capacity_factor · T · top_k / E.

Two execution paths:
  * dense/global (`moe_mlp_dense`) — single-device semantics; what unit
    tests and the non-mesh path use. Under pjit the global scatter cannot
    be partitioned (token-sharded updates into an expert-sharded buffer)
    and degenerates into per-layer all-reduces of the whole (E, C, d)
    buffer — measured at ~45 TB/device/step on qwen3-moe (EXPERIMENTS.md
    §Perf).
  * expert-parallel shard_map (`moe_mlp_ep`) — activations are replicated
    over "model" and sharded over the batch axes, so each device already
    holds its tokens and an E/TP slice of experts: route locally against
    all-gathered router logits, dispatch *locally*, run the local expert
    GEMMs, and combine with one psum over "model" (each token's experts
    live on exactly one model shard). Cross-device volume drops from
    O(E·C·d) to O(T_loc·d) per layer.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.common import ParamBuilder, current_mesh_and_rules, shard


def init_mlp(pb: ParamBuilder, cfg: ModelConfig, name: str = "mlp"):
    D, F = cfg.d_model, cfg.d_ff
    gated = cfg.mlp_type in ("swiglu", "geglu")
    with pb.scope(name):
        if cfg.mlp_type == "moe":
            E = cfg.n_experts
            Fe = cfg.d_ff
            pb("router", (D, E), ("embed", "experts"), dtype=jnp.float32)
            pb("w_gate", (E, D, Fe), ("experts", "embed", "expert_mlp"))
            pb("w_up", (E, D, Fe), ("experts", "embed", "expert_mlp"))
            pb("w_down", (E, Fe, D), ("experts", "expert_mlp", "embed"))
            if cfg.moe_shared_expert:
                pb("ws_gate", (D, Fe), ("embed", "mlp"))
                pb("ws_up", (D, Fe), ("embed", "mlp"))
                pb("ws_down", (Fe, D), ("mlp", "embed"))
        else:
            if gated:
                pb("w_gate", (D, F), ("embed", "mlp"))
            pb("w_up", (D, F), ("embed", "mlp"))
            pb("w_down", (F, D), ("mlp", "embed"))


def _act(h, kind: str):
    if kind in ("swiglu",):
        return jax.nn.silu(h)
    if kind in ("geglu", "gelu"):
        return jax.nn.gelu(h)
    if kind == "squared_relu":
        r = jax.nn.relu(h)
        return r * r
    raise ValueError(kind)


def _dense_mlp(p, x, kind: str):
    if kind in ("swiglu", "geglu"):
        h = _act(jnp.einsum("bsd,df->bsf", x, p["w_gate"]), kind) \
            * jnp.einsum("bsd,df->bsf", x, p["w_up"])
    else:
        h = _act(jnp.einsum("bsd,df->bsf", x, p["w_up"]), kind)
    h = shard(h, "batch", None, "mlp")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


def _shared_expert(p, x):
    h = jax.nn.silu(jnp.einsum("bsd,df->bsf", x, p["ws_gate"])) \
        * jnp.einsum("bsd,df->bsf", x, p["ws_up"])
    return jnp.einsum("bsf,fd->bsd", h, p["ws_down"])


def _dispatch_compute(xt, gate_vals, expert_ids, w_gate, w_up, w_down,
                      C: int, e_offset=0):
    """Sort-based capacity dispatch + grouped GEMM + weighted combine.

    xt: (T, D); expert_ids/gate_vals: (T, K) *local* expert indices in
    [0, E_loc) (entries outside the range are dropped via the capacity
    mask); weights: (E_loc, D, F)/(E_loc, F, D). Returns (T, D).
    """
    T, D = xt.shape
    E_loc = w_gate.shape[0]
    K = expert_ids.shape[1]
    flat_e = expert_ids.reshape(T * K) - e_offset
    in_range = (flat_e >= 0) & (flat_e < E_loc)
    flat_e = jnp.clip(flat_e, 0, E_loc - 1)
    flat_t = jnp.repeat(jnp.arange(T), K)
    flat_g = jnp.where(in_range, gate_vals.reshape(T * K), 0.0)

    order = jnp.argsort(jnp.where(in_range, flat_e, E_loc))
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    s_in = in_range[order]
    counts = jnp.bincount(jnp.where(in_range, flat_e, E_loc), length=E_loc + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * K) - starts[jnp.where(s_in, se, E_loc)]
    keep = s_in & (pos < C)
    pos_c = jnp.clip(pos, 0, C - 1)

    # .add, not .set: dropped/out-of-range rows clip onto occupied slots and
    # must contribute nothing rather than clobber them with zeros.
    buf = jnp.zeros((E_loc, C, D), xt.dtype)
    buf = buf.at[se, pos_c].add(jnp.where(keep[:, None], xt[st], 0.0))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, w_gate)) \
        * jnp.einsum("ecd,edf->ecf", buf, w_up)
    out_buf = jnp.einsum("ecf,efd->ecd", h, w_down)
    contrib = out_buf[se, pos_c] * jnp.where(keep, sg, 0.0)[:, None]
    return jnp.zeros((T, D), out_buf.dtype).at[st].add(contrib)


def _route(xt, router, K):
    logits = (xt.astype(jnp.float32) @ router).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = jax.lax.top_k(probs, K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    return gate_vals, expert_ids


def moe_mlp_dense(p, x, cfg: ModelConfig):
    """Global-semantics MoE (single device / no mesh)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    T = B * S
    C = max(1, math.ceil(cfg.moe_capacity_factor * T * K / E))
    xt = x.reshape(T, D)
    gate_vals, expert_ids = _route(xt, p["router"], K)
    y = _dispatch_compute(xt, gate_vals, expert_ids,
                          p["w_gate"], p["w_up"], p["w_down"], C)
    y = y.reshape(B, S, D)
    if cfg.moe_shared_expert:
        y = y + _shared_expert(p, x)
    return y.astype(x.dtype)


def moe_mlp_ep(p, x, cfg: ModelConfig, mesh, rules):
    """Expert-parallel MoE via shard_map (see module docstring)."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.moe_top_k
    model_ax = rules.experts
    batch_ax = rules.batch
    n_model = mesh.shape[model_ax]
    E_loc = E // n_model
    T_glob = B * S

    def local(x_l, router, wg, wu, wd):
        Bl, Sl, Dl = x_l.shape
        Tl = Bl * Sl
        xt = x_l.reshape(Tl, Dl)
        # router is expert-sharded: gather the full score row per token
        logits_loc = (xt.astype(jnp.float32) @ router)
        logits = jax.lax.all_gather(logits_loc, model_ax, axis=1, tiled=True)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, expert_ids = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                            1e-9)
        # per-(data-shard × expert-shard) capacity keeps memory flat
        C = max(1, math.ceil(cfg.moe_capacity_factor * Tl * K / E))
        e_offset = jax.lax.axis_index(model_ax) * E_loc
        y = _dispatch_compute(xt, gate_vals, expert_ids, wg, wu, wd, C,
                              e_offset=e_offset)
        # each token's experts live on exactly one model shard → sum
        y = jax.lax.psum(y, model_ax)
        return y.reshape(Bl, Sl, Dl)

    y = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(batch_ax, None, None), P(None, model_ax),
                  P(model_ax, None, None), P(model_ax, None, None),
                  P(model_ax, None, None)),
        out_specs=P(batch_ax, None, None),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if cfg.moe_shared_expert:
        y = y + _shared_expert(p, x)
    return y.astype(x.dtype)


def mlp_forward(p, x, cfg: ModelConfig):
    if cfg.mlp_type == "moe":
        state = current_mesh_and_rules()
        if state is not None and state[1].experts is not None \
                and cfg.n_experts % state[0].shape[state[1].experts] == 0:
            return moe_mlp_ep(p, x, cfg, state[0], state[1])
        return moe_mlp_dense(p, x, cfg)
    return _dense_mlp(p, x, cfg.mlp_type)


moe_mlp = moe_mlp_dense  # back-compat alias
