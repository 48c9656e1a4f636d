"""Mixture-of-Experts FFN with expert parallelism (DESIGN.md §3).

Three interchangeable implementations (tests assert they agree):

* ``moe_reference`` — dense per-expert masked compute; O(E·N·D·F) FLOPs, used
  as the numerics oracle and for tiny CPU models.
* ``moe_ep_train`` — production path: shard_map over the whole mesh; tokens
  are (dp × sp)-sharded, experts are sharded over ``model``. Dispatch is a
  static-capacity all_to_all along ``model``: per-device one-hot cumsum
  assigns each (token, slot) pair a position in its destination rank's
  buffer; overflowing pairs are dropped GShard-style (gates renormalized
  first, drop statistics returned). FSDP-sharded expert weights are
  all-gathered along ``fsdp`` inside the block (ZeRO-3).
* ``moe_ep_decode`` — decode path (few tokens, replicated over ``model``):
  no all_to_all; every model rank computes only the pairs routed to its own
  local experts and contributes via psum. Traffic = active expert weights,
  which is the decode roofline term.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


Array = jax.Array


@dataclasses.dataclass(frozen=True)
class MoEDims:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int
    cap_factor: float = 2.0


def _router(tokens: Array, w_router: Array, top_k: int):
    """tokens [N, D] -> (gates [N,k] fp32 normalized, eids int32 [N,k])."""
    logits = jnp.einsum("nd,de->ne", tokens.astype(jnp.float32),
                        w_router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    return gates, eids.astype(jnp.int32)


def _expert_ffn(buf: Array, wi_g: Array, wi_u: Array, wo: Array) -> Array:
    """buf [E, C, D] -> [E, C, D]; SwiGLU per expert."""
    g = jnp.einsum("ecd,edf->ecf", buf, wi_g)
    u = jnp.einsum("ecd,edf->ecf", buf, wi_u)
    h = jax.nn.silu(g) * u
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _pack(keys: Array, n_groups: int, cap: int):
    """Assign each item a slot (group, pos) via one-hot cumsum; -1 keys and
    overflow are dropped (returned pos == cap)."""
    oh = jax.nn.one_hot(keys, n_groups, dtype=jnp.int32)   # [N, G]; -1 -> 0s
    pos = (jnp.cumsum(oh, axis=0) - 1) * oh                # [N, G]
    pos = pos.max(axis=1)                                  # position in group
    pos = jnp.where((keys < 0) | (pos >= cap), cap, pos)
    return pos


def moe_reference(x: Array, w_router: Array, wi_g: Array, wi_u: Array,
                  wo: Array, dims: MoEDims) -> Array:
    """Oracle: every expert runs over all tokens, masked combine."""
    B, S, D = x.shape
    tokens = x.reshape(-1, D)
    gates, eids = _router(tokens, w_router, dims.top_k)
    mask = jax.nn.one_hot(eids, dims.n_experts, dtype=gates.dtype)  # [N,k,E]
    comb = (gates[..., None] * mask).sum(axis=1)                    # [N,E]
    outs = _expert_ffn(jnp.broadcast_to(tokens, (dims.n_experts,) + tokens.shape),
                       wi_g, wi_u, wo)                              # [E,N,D]
    y = jnp.einsum("ne,end->nd", comb, outs.astype(gates.dtype))
    return y.reshape(B, S, D).astype(x.dtype)


# ----------------------------------------------------------------- EP (train)


def moe_ep_train(x: Array, w_router: Array, wi_g: Array, wi_u: Array,
                 wo: Array, dims: MoEDims, mesh: Mesh, *,
                 dp: tuple, tp: str, fsdp: tuple) -> Array:
    """x [B, S, D] sharded P(dp, tp, None); experts sharded over ``tp``."""
    E, k = dims.n_experts, dims.top_k
    tp_size = mesh.shape[tp]
    e_loc = E // tp_size
    fsdp_axes = tuple(a for a in fsdp if mesh.shape[a] > 1)
    d_shard = dims.d_model % jax.tree_util.tree_reduce(
        lambda a, b: a * b, [mesh.shape[a] for a in fsdp_axes], 1) == 0 \
        if fsdp_axes else False

    w_spec_in = P(tp, fsdp if d_shard else None, None)
    w_spec_out = P(tp, None, fsdp if d_shard else None)

    def block(x_loc, wr, wig, wiu, wol):
        Bl, Sl, D = x_loc.shape
        n_loc = Bl * Sl
        cap_s = max(1, int(n_loc * k / tp_size * dims.cap_factor))
        cap_e = max(1, int(tp_size * cap_s / e_loc * dims.cap_factor))
        # ZeRO-3: re-materialize full expert weights for this model rank
        if d_shard:
            for ax in reversed(fsdp_axes):
                wig = jax.lax.all_gather(wig, ax, axis=1, tiled=True)
                wiu = jax.lax.all_gather(wiu, ax, axis=1, tiled=True)
                wol = jax.lax.all_gather(wol, ax, axis=2, tiled=True)
        tokens = x_loc.reshape(n_loc, D)
        gates, eids = _router(tokens, wr, k)
        dest = eids // e_loc                                 # [n_loc, k]
        flat_dest = dest.reshape(-1)
        pos_s = _pack(flat_dest, tp_size, cap_s)             # [n_loc*k]
        slot = flat_dest * cap_s + jnp.minimum(pos_s, cap_s - 1)
        dropped_s = pos_s >= cap_s
        slot = jnp.where(dropped_s, tp_size * cap_s, slot)   # drop bucket
        tok_rep = jnp.repeat(tokens, k, axis=0)
        send_x = jnp.zeros((tp_size * cap_s + 1, D), tokens.dtype) \
            .at[slot].set(tok_rep, mode="drop")[:-1].reshape(tp_size, cap_s, D)
        e_local = (eids % e_loc).reshape(-1)
        send_e = jnp.full((tp_size * cap_s + 1,), -1, jnp.int32) \
            .at[slot].set(e_local, mode="drop")[:-1].reshape(tp_size, cap_s)
        # dispatch
        recv_x = jax.lax.all_to_all(send_x, tp, 0, 0, tiled=False)
        recv_e = jax.lax.all_to_all(send_e, tp, 0, 0, tiled=False)
        # group by local expert
        re = recv_e.reshape(-1)
        pos_e = _pack(re, e_loc, cap_e)
        eslot = re * cap_e + jnp.minimum(pos_e, cap_e - 1)
        eslot = jnp.where((re < 0) | (pos_e >= cap_e), e_loc * cap_e, eslot)
        buf = jnp.zeros((e_loc * cap_e + 1, D), recv_x.dtype) \
            .at[eslot].set(recv_x.reshape(-1, D), mode="drop")[:-1] \
            .reshape(e_loc, cap_e, D)
        out_buf = _expert_ffn(buf, wig, wiu, wol)
        # un-group: value for each recv slot
        back = jnp.take(out_buf.reshape(-1, D),
                        jnp.minimum(eslot, e_loc * cap_e - 1), axis=0)
        back = jnp.where((eslot >= e_loc * cap_e)[:, None], 0.0, back)
        back = back.reshape(tp_size, cap_s, D)
        ret = jax.lax.all_to_all(back, tp, 0, 0, tiled=False)
        # combine at the owner
        pair_out = jnp.take(ret.reshape(-1, D),
                            jnp.minimum(slot, tp_size * cap_s - 1), axis=0)
        pair_out = jnp.where(dropped_s[:, None], 0.0, pair_out)
        y = (pair_out.reshape(n_loc, k, D) *
             gates[..., None].astype(pair_out.dtype)).sum(axis=1)
        return y.reshape(Bl, Sl, D).astype(x_loc.dtype)

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(dp, tp, None), P(), w_spec_in, w_spec_in, w_spec_out),
        out_specs=P(dp, tp, None),
        check_vma=False,
    )(x, w_router, wi_g, wi_u, wo)


# ---------------------------------------------------------------- EP (decode)


def moe_ep_decode(x: Array, w_router: Array, wi_g: Array, wi_u: Array,
                  wo: Array, dims: MoEDims, mesh: Mesh, *,
                  dp: tuple, tp: str, fsdp: tuple) -> Array:
    """x [B, 1, D] replicated over ``model``; batch over dp if divisible.

    Expert weights stay ZeRO-sharded over ``fsdp`` at rest (a 1T-param model
    cannot keep resident full expert copies per model rank: 384e/16 = 24
    experts x 7168 x 2048 x 3 = 128 GiB/chip) and are all-gathered per layer
    inside the block — the gather traffic IS the active-weight traffic that
    bounds batched MoE decode.
    """
    E, k = dims.n_experts, dims.top_k
    tp_size = mesh.shape[tp]
    e_loc = E // tp_size
    fsdp_axes = tuple(a for a in fsdp if mesh.shape[a] > 1)
    import numpy as _np
    d_shard = (dims.d_model % int(_np.prod([mesh.shape[a] for a in fsdp_axes]))
               == 0) if fsdp_axes else False
    w_spec_in = P(tp, fsdp if d_shard else None, None)
    w_spec_out = P(tp, None, fsdp if d_shard else None)
    b_axes = dp if x.shape[0] % max(1, jax.tree_util.tree_reduce(
        lambda a, b: a * b, [mesh.shape[a] for a in dp], 1)) == 0 else None

    def block(x_loc, wr, wig, wiu, wol):
        if d_shard:
            for ax in reversed(fsdp_axes):
                wig = jax.lax.all_gather(wig, ax, axis=1, tiled=True)
                wiu = jax.lax.all_gather(wiu, ax, axis=1, tiled=True)
                wol = jax.lax.all_gather(wol, ax, axis=2, tiled=True)
        Bl, Sl, D = x_loc.shape
        n_loc = Bl * Sl
        cap_e = max(1, n_loc * k)                  # no dropping at decode
        m = jax.lax.axis_index(tp)
        tokens = x_loc.reshape(n_loc, D)
        gates, eids = _router(tokens, wr, k)
        mine = (eids // e_loc) == m                # [n_loc, k]
        e_local = jnp.where(mine, eids % e_loc, -1).reshape(-1)
        pos_e = _pack(e_local, e_loc, cap_e)
        eslot = e_local * cap_e + jnp.minimum(pos_e, cap_e - 1)
        eslot = jnp.where(e_local < 0, e_loc * cap_e, eslot)
        tok_rep = jnp.repeat(tokens, k, axis=0)
        buf = jnp.zeros((e_loc * cap_e + 1, D), tokens.dtype) \
            .at[eslot].set(tok_rep, mode="drop")[:-1].reshape(e_loc, cap_e, D)
        out_buf = _expert_ffn(buf, wig, wiu, wol)
        back = jnp.take(out_buf.reshape(-1, D),
                        jnp.minimum(eslot, e_loc * cap_e - 1), axis=0)
        back = jnp.where((eslot >= e_loc * cap_e)[:, None], 0.0, back)
        y = (back.reshape(n_loc, k, D) *
             gates[..., None].astype(back.dtype)).sum(axis=1)
        y = jax.lax.psum(y, tp)
        return y.reshape(Bl, Sl, D).astype(x_loc.dtype)

    return jax.shard_map(
        block, mesh=mesh,
        in_specs=(P(b_axes, None, None), P(), w_spec_in, w_spec_in,
                  w_spec_out),
        out_specs=P(b_axes, None, None),
        check_vma=False,
    )(x, w_router, wi_g, wi_u, wo)
