"""Mixture-of-Experts FFN: top-k routing with capacity buffers (+ shared experts).

Dispatch is the scatter/gather formulation (GShard capacity semantics without
the (T, E, C) one-hot): tokens are scattered into per-expert capacity buffers
(E, C, d) via computed slots, experts run as one batched einsum (EP: the E dim
shards over the ``model``/``expert`` mesh axis), results gather back weighted
by router probabilities.  Tokens beyond capacity are dropped (standard
capacity-factor semantics); shared experts (DeepSeek-style) are a fused dense
FFN that always runs.

Returns a Switch-style load-balance auxiliary loss.
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.models.config import MoECfg
from repro.models.layers.mlp import init_mlp, mlp_forward

__all__ = ["init_moe", "moe_forward"]


def init_moe(key, d_model: int, cfg: MoECfg, act: str, dtype=jnp.bfloat16):
    kr, ke, kg, ko, ks = jax.random.split(key, 5)
    E, f = cfg.n_experts, cfg.d_ff_expert
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(f)
    p = {
        "router": (jax.random.normal(kr, (d_model, E)) * s_in).astype(jnp.float32),
        "w_in": (jax.random.normal(ke, (E, d_model, f)) * s_in).astype(dtype),
        "w_out": (jax.random.normal(ko, (E, f, d_model)) * s_out).astype(dtype),
    }
    if act in ("silu_gated", "gelu_gated"):
        p["w_gate"] = (jax.random.normal(kg, (E, d_model, f)) * s_in).astype(dtype)
    if cfg.n_shared:
        p["shared"] = init_mlp(ks, d_model, cfg.n_shared * f, act, dtype)
    return p


def _expert_ffn(p, h, act: str):
    """h: (E, C, d) -> (E, C, d), batched over experts."""
    u = jnp.einsum("ecd,edf->ecf", h, p["w_in"])
    if act in ("silu_gated", "gelu_gated"):
        g = jnp.einsum("ecd,edf->ecf", h, p["w_gate"])
        u = (jax.nn.silu(g) if act == "silu_gated" else jax.nn.gelu(g)) * u
    elif act == "squared_relu":
        u = jnp.square(jax.nn.relu(u))
    else:
        u = jax.nn.gelu(u)
    return jnp.einsum("ecf,efd->ecd", u, p["w_out"])


def _dispatch_compute_combine(p, xl, cfg: MoECfg, act: str, e_base, E_loc: int, C: int):
    """Route local tokens to local experts with capacity C (no comms).

    xl (T_loc, d); expert weights in ``p`` already local (E_loc, d, f).
    Returns (partial y (T_loc, d) — contributions of local experts only,
    me (E,), ce (E,) for the aux loss).
    """
    T_loc, d = xl.shape
    E, K = cfg.n_experts, cfg.top_k
    logits = xl.astype(jnp.float32) @ p["router"]  # (T_loc, E) fp32 routing
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(0)
    ce = jax.nn.one_hot(top_idx[:, 0], E, dtype=jnp.float32).mean(0)

    rel = top_idx - e_base  # (T_loc, K) index into local experts
    mine = (rel >= 0) & (rel < E_loc)
    flat_rel = jnp.where(mine, rel, E_loc).reshape(-1)  # E_loc = dump bucket
    oh = jax.nn.one_hot(flat_rel, E_loc + 1, dtype=jnp.int32)
    pos = ((jnp.cumsum(oh, axis=0) - 1) * oh).sum(-1)
    valid = mine.reshape(-1) & (pos < C)
    slot = jnp.where(valid, flat_rel * C + pos, E_loc * C)

    xrep = jnp.broadcast_to(xl[:, None, :], (T_loc, K, d)).reshape(T_loc * K, d)
    buf = jnp.zeros((E_loc * C + 1, d), xl.dtype).at[slot].set(xrep)
    h = buf[: E_loc * C].reshape(E_loc, C, d)
    o = _expert_ffn(p, h, act)
    o_flat = jnp.concatenate([o.reshape(E_loc * C, d), jnp.zeros((1, d), o.dtype)])
    y_tk = o_flat[slot] * valid[:, None].astype(o.dtype)
    y = (y_tk.reshape(T_loc, K, d) * top_w[..., None].astype(xl.dtype)).sum(1)
    return y, me, ce


def _moe_forward_shard_map(
    p, xf, cfg: MoECfg, act: str, mesh, wg=None
) -> Tuple[jax.Array, jax.Array]:
    """EP dispatch under shard_map: tokens sharded over the data axes, experts
    over ``model``.  Dispatch buffers are per-shard ((E/M) x C_loc x d — MBs,
    not GiBs), the only communication is one psum over ``model`` to combine
    expert contributions (replacing the dense-FFN TP reduction).
    """
    from jax.sharding import PartitionSpec as P

    da = tuple(a for a in ("pod", "data") if a in mesh.shape)
    M = mesh.shape["model"]
    D = 1
    for a in da:
        D *= mesh.shape[a]
    T, d = xf.shape
    E, K = cfg.n_experts, cfg.top_k
    E_loc = E // M
    T_loc = T // D
    C_loc = max(1, int(math.ceil(T_loc * K / E * cfg.capacity_factor)))

    wspecs = {
        "router": P(None, None),
        "w_in": P("model", None, None),
        "w_out": P("model", None, None),
    }
    if "w_gate" in p:
        wspecs["w_gate"] = P("model", None, None)
    if wg is not None:
        # int8-compressed FSDP gather of the expert weights (Perf change #2)
        pw = {"router": jax.lax.with_sharding_constraint(
            p["router"], jax.sharding.NamedSharding(mesh, wspecs["router"]))}
        for k in ("w_in", "w_gate", "w_out"):
            if k in p:
                pw[k] = wg(p[k], "moe")
    else:
        pw = {k: jax.lax.with_sharding_constraint(
            p[k], jax.sharding.NamedSharding(mesh, s)) for k, s in wspecs.items()}

    def local_fn(weights, xl):
        j = jax.lax.axis_index("model")
        y, me, ce = _dispatch_compute_combine(
            weights, xl, cfg, act, j * E_loc, E_loc, C_loc
        )
        y = jax.lax.psum(y, "model")
        me = jax.lax.pmean(me, da) if da else me
        ce = jax.lax.pmean(ce, da) if da else ce
        aux = E * jnp.sum(me * ce)
        return y, aux

    y, aux = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(wspecs, P(da, None)),
        out_specs=(P(da, None), P()),
        check_vma=False,
    )(pw, xf)
    return y, aux


def moe_forward(
    p, x, cfg: MoECfg, act: str, shard_fn=lambda a, k: a, wg=None
) -> Tuple[jax.Array, jax.Array]:
    """x: (B, L, d) -> (y, aux_loss).

    When the caller's ``shard_fn`` carries a mesh (distributed runs) and the
    token count divides the data axes, routing runs under shard_map (EP with
    per-shard capacity buffers — see ``_moe_forward_shard_map``); otherwise
    the single-device pjit scatter path below is used (smoke tests, tiny
    decode batches)."""
    B, L, d = x.shape
    T = B * L
    E, K = cfg.n_experts, cfg.top_k

    mesh = getattr(shard_fn, "mesh", None)
    if mesh is not None and "model" in mesh.shape and E % mesh.shape["model"] == 0:
        da = tuple(a for a in ("pod", "data") if a in mesh.shape)
        D = 1
        for a in da:
            D *= mesh.shape[a]
        if T % D == 0 and T >= D:
            xf = x.reshape(T, d)
            y, aux = _moe_forward_shard_map(p, xf, cfg, act, mesh, wg)
            if "shared" in p:
                y = y + mlp_forward(p["shared"], xf, act)
            return y.reshape(B, L, d), aux

    xf = shard_fn(x.reshape(T, d), "moe_tokens")

    logits = (xf.astype(jnp.float32)) @ p["router"]  # (T, E) fp32 routing
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_idx = jax.lax.top_k(probs, K)  # (T, K)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- load-balance aux (Switch): E * sum_e f_e * P_e ----------------
    me = probs.mean(0)  # (E,)
    one_hot_top1 = jax.nn.one_hot(top_idx[:, 0], E, dtype=jnp.float32)
    ce = one_hot_top1.mean(0)
    aux = E * jnp.sum(me * ce)

    # ---- capacity slots -------------------------------------------------
    C = max(1, int(math.ceil(T * K / E * cfg.capacity_factor)))
    flat_e = top_idx.reshape(-1)  # (T*K,)
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)  # (T*K, E)
    pos_in_e = (jnp.cumsum(oh, axis=0) - 1) * oh  # running index per expert
    pos = pos_in_e.sum(-1)  # (T*K,)
    valid = pos < C
    slot = jnp.where(valid, flat_e * C + pos, E * C)  # E*C = drop row

    # ---- dispatch -> expert compute -> combine -------------------------
    xrep = jnp.broadcast_to(xf[:, None, :], (T, K, d)).reshape(T * K, d)
    xrep = shard_fn(xrep, "moe_tokens")
    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xrep)
    h = shard_fn(buf[: E * C].reshape(E, C, d), "moe_buf")
    o = shard_fn(_expert_ffn(p, h, act), "moe_buf")
    o_flat = jnp.concatenate([o.reshape(E * C, d), jnp.zeros((1, d), o.dtype)])
    y_tk = shard_fn(o_flat[slot], "moe_tokens")  # dropped tokens read zeros
    y = (y_tk.reshape(T, K, d) * top_w[..., None].astype(x.dtype)).sum(1)

    if "shared" in p:
        y = y + mlp_forward(p["shared"], xf, act)
    return y.reshape(B, L, d), aux
