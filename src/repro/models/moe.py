"""Mixture-of-Experts: top-k routing with fixed expert capacity.

Sort-free deterministic dispatch: tokens pick top-k experts; each (token,
slot) gets a position within its expert via a cumulative one-hot count;
tokens beyond expert capacity are dropped (their combine weight is zeroed) —
GShard semantics. Expert weights are sharded over "model" (expert
parallelism); the token->expert buffer movement lowers to all-to-all-style
collectives under GSPMD.

Shared experts (DeepSeek) run densely over all tokens.

Load-balance auxiliary loss (Switch-style) is returned to the train loss;
the LPT analysis in distributed/partition.py consumes the same per-expert
load counts for placement studies (DESIGN.md §5 crossover).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import layers
from repro.distributed import sharding as _shard


def moe_init(key, cfg) -> dict:
    D = cfg.d_model
    E, Fe = cfg.n_experts, cfg.d_ff_expert
    ks = jax.random.split(key, 5)
    out_scale = 1.0 / math.sqrt(2 * cfg.n_layers)
    p = {
        "router": layers.dense_init(ks[0], (D, E), scale=0.5),
        "wg": layers.dense_init(ks[1], (E, D, Fe)),
        "wu": layers.dense_init(ks[2], (E, D, Fe)),
        "wo": layers.dense_init(ks[3], (E, Fe, D), scale=out_scale),
    }
    if cfg.n_shared_experts:
        Fs = cfg.n_shared_experts * Fe
        kk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "wg": layers.dense_init(kk[0], (D, Fs)),
            "wu": layers.dense_init(kk[1], (D, Fs)),
            "wo": layers.dense_init(kk[2], (Fs, D), scale=out_scale),
        }
    return p


def moe_apply(cfg, p, x) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, S, D) -> (out, aux_loss)."""
    dt = x.dtype
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    xt = x.reshape(T, D)

    logits = (xt.astype(jnp.float32) @ p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)                    # (T, E)
    gate_vals, expert_idx = jax.lax.top_k(probs, K)            # (T, K)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9
    )                                                          # renormalize

    # Switch-style load-balance loss
    me = probs.mean(0)                                         # (E,)
    one_hot_top1 = jax.nn.one_hot(expert_idx[:, 0], E)
    ce = one_hot_top1.mean(0)
    aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

    # ---- capacity dispatch ------------------------------------------------
    C = int(math.ceil(T * K * cfg.capacity_factor / E))
    C = max(8, -(-C // 8) * 8)
    flat_e = expert_idx.reshape(-1)                            # (T*K,)
    # position of each (token, slot) within its expert: running count
    eo = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)            # (T*K, E)
    pos_in_e = (jnp.cumsum(eo, axis=0) - eo)                   # exclusive
    pos = jnp.take_along_axis(pos_in_e, flat_e[:, None], axis=1)[:, 0]
    keep = pos < C
    gate_keep = jnp.where(
        keep.reshape(T, K), gate_vals.astype(jnp.float32), 0.0
    )

    # scatter tokens into (E, C, D) buffers
    safe_pos = jnp.where(keep, pos, C - 1)
    buf = jnp.zeros((E, C, D), dt)
    src = jnp.repeat(xt, K, axis=0)                            # (T*K, D)
    src = jnp.where(keep[:, None], src, 0)
    buf = buf.at[flat_e, safe_pos].add(src)                    # dup-safe: add

    # expert FFN (E sharded over "model")
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"].astype(dt)))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wu"].astype(dt))
    yb = jnp.einsum("ecf,efd->ecd", g * u, p["wo"].astype(dt))  # (E, C, D)

    # combine: gather back and weight.
    # §Perf iteration 7 (REFUTED, reverted): forcing token-sharding through
    # the dispatch/combine via hints made GSPMD's gather fallbacks worse
    # (33.5s -> 58.4s collective on dbrx train). The identified real fix is
    # an explicit shard_map all-to-all dispatch (MaxText-style) — recorded
    # as the top follow-up in EXPERIMENTS.md §Perf.
    y_tok = yb[flat_e, safe_pos].reshape(T, K, D)
    y = jnp.einsum("tkd,tk->td", y_tok.astype(jnp.float32), gate_keep)
    y = y.astype(dt)

    if cfg.n_shared_experts:
        y = y + layers.mlp_apply(cfg, p["shared"], xt)
    return y.reshape(B, S, D), aux


def expert_load_counts(cfg, p, x) -> jnp.ndarray:
    """Per-expert top-1 token counts (for the LPT placement analysis)."""
    T = x.shape[0] * x.shape[1]
    logits = x.reshape(T, -1).astype(jnp.float32) @ p["router"].astype(
        jnp.float32
    )
    top1 = jnp.argmax(logits, -1)
    return jnp.bincount(top1, length=cfg.n_experts)


# ---------------------------------------------------------------- a2a MoE
def moe_apply_a2a(cfg, p, x, mesh) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Expert-parallel MoE with an explicit shard_map all-to-all exchange.

    The GSPMD gather/scatter dispatch (moe_apply) lowers the expert->token
    combine into a per-layer all-reduce of the full (T·K, D/TP) tensor
    (§Perf iteration 7). This path makes the token<->expert movement
    explicit: tokens are split over the "model" axis, each rank builds one
    send buffer per destination expert-rank, `lax.all_to_all` exchanges
    them, local experts run, and a second all_to_all returns results —
    every token crosses the wire exactly twice, in the compute dtype.

    Ranks with E/TP > 1 local experts evaluate each local expert on the
    whole received buffer and select (overcompute factor E/TP; exact for
    dbrx's 16e/16 ranks — noted in EXPERIMENTS).
    """
    from jax.sharding import PartitionSpec as P

    from jax import shard_map
    from repro.distributed import sharding as _sh

    dt = x.dtype
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    bd = _sh.batch_axes(mesh)
    M = mesh.shape.get(_sh.TP, 1)
    n_bd = int(np.prod([mesh.shape[a] for a in bd])) if bd else 1
    if M == 1 or E % M or (T // max(n_bd, 1)) % M:
        return moe_apply(cfg, p, x)               # fall back to GSPMD path
    E_loc = E // M
    xt = x.reshape(T, D)

    def f(x_loc, router, wg, wu, wo):
        # x_loc: (T_loc, D) data-sharded, replicated over model
        m = jax.lax.axis_index(_sh.TP)
        T_loc = x_loc.shape[0]
        T2 = T_loc // M
        x_my = jax.lax.dynamic_slice_in_dim(x_loc, m * T2, T2, 0)

        logits = x_my.astype(jnp.float32) @ router.astype(jnp.float32)
        probs = jax.nn.softmax(logits, -1)                     # (T2, E)
        gate_vals, eidx = jax.lax.top_k(probs, K)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)
        # load-balance aux (global mean via psum over all axes)
        me_sum = probs.sum(0)
        ce_sum = jax.nn.one_hot(eidx[:, 0], E).sum(0)
        axes_all = tuple(bd) + (_sh.TP,)
        me = jax.lax.psum(me_sum, axes_all) / T
        ce = jax.lax.psum(ce_sum, axes_all) / T
        aux = cfg.router_aux_coef * E * jnp.sum(me * ce)

        flat_e = eidx.reshape(-1)                              # (T2*K,)
        dest = flat_e // E_loc                                 # rank
        e_loc = flat_e % E_loc                                 # local expert
        C2 = int(math.ceil(T2 * K * cfg.capacity_factor / M))
        C2 = max(8, -(-C2 // 8) * 8)
        oh = jax.nn.one_hot(dest, M, dtype=jnp.int32)
        pos = jnp.take_along_axis(
            jnp.cumsum(oh, 0) - oh, dest[:, None], 1)[:, 0]
        keep = pos < C2
        safe_pos = jnp.where(keep, pos, C2 - 1)
        gate_keep = jnp.where(keep.reshape(T2, K),
                              gate_vals.astype(jnp.float32), 0.0)

        src = jnp.repeat(x_my, K, axis=0)
        src = jnp.where(keep[:, None], src, 0)
        send = jnp.zeros((M, C2, D), dt).at[dest, safe_pos].add(src)
        send_e = jnp.zeros((M, C2), jnp.int32).at[dest, safe_pos].max(
            jnp.where(keep, e_loc, 0))
        recv = jax.lax.all_to_all(send, _sh.TP, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, _sh.TP, 0, 0, tiled=False)
        tok = recv.reshape(M * C2, D)

        def one_expert(le):
            g = jax.nn.silu(tok @ wg[le].astype(dt))
            u = tok @ wu[le].astype(dt)
            return (g * u) @ wo[le].astype(dt)

        yb = one_expert(0)
        for le in range(1, E_loc):
            yb = jnp.where(
                (recv_e.reshape(-1) == le)[:, None], one_expert(le), yb)
        back = jax.lax.all_to_all(
            yb.reshape(M, C2, D), _sh.TP, 0, 0, tiled=False)
        y_tok = back[dest, safe_pos].reshape(T2, K, D)
        y_my = jnp.einsum("tkd,tk->td", y_tok.astype(jnp.float32),
                          gate_keep).astype(dt)
        y_full = jax.lax.all_gather(y_my, _sh.TP, axis=0,
                                    tiled=False).reshape(T_loc, D)
        return y_full, aux

    in_specs = (
        P(bd if bd else None, None),
        P(None, None),
        P(_sh.TP, None, None), P(_sh.TP, None, None), P(_sh.TP, None, None),
    )
    out_specs = (P(bd if bd else None, None), P())
    # check_vma=False: y_full is made replicated-over-model by the final
    # all_gather, which the static replication checker cannot infer.
    y, aux = shard_map(f, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)(
        xt, p["router"], p["wg"], p["wu"], p["wo"])
    y = y.reshape(B, S, D)
    if cfg.n_shared_experts:
        y = y + layers.mlp_apply(cfg, p["shared"], x.reshape(B, S, D))
    return y, aux
