"""Expert-parallel MoE via ``shard_map`` + explicit all-to-all (H1 endgame).

The GSPMD-partitioned scatter/gather dispatch replicates u32 index grids
(EXPERIMENTS.md §Perf H1 iter 3/4); this module takes manual control: every
device routes ITS tokens, packs per-destination-shard capacity buffers, and a
single ``all_to_all`` over the ``model`` axis moves exactly the token payload
(T·k·d bytes globally) each way.

Layout contract (rule set ``fsdp2d_a2a``):
  x       : (T, d)        sharded P(("data","model"))  — T_loc = T/256 tokens
  router  : (d, E)        replicated
  wi/wo   : (E, d, f)     sharded P("model")           — E_loc experts/device
Inside the shard_map every array is the per-device block; collectives are
explicit (`all_to_all`, `psum`).  Differentiable (shard_map grads thread the
transposed collectives automatically).
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .config import ModelConfig


def _local_dispatch(xt, logits, n_shards: int, e_loc: int, cap: int, k: int):
    """Per-device routing + packing.  Returns (send buffer
    (n_shards, e_loc, cap, d), combine metadata)."""
    T_my, d = xt.shape
    E = n_shards * e_loc
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)
    gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True), 1e-9)
    flat_e = expert_idx.reshape(T_my * k)
    flat_g = gate_vals.reshape(T_my * k)
    # position within (destination expert) among MY tokens — sort ranking
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(E, dtype=jnp.int32))
    seg_pos = jnp.arange(T_my * k, dtype=jnp.int32) - starts[sorted_e]
    pos = jnp.zeros((T_my * k,), jnp.int32).at[order].set(seg_pos)
    keep = pos < cap
    slot = jnp.where(keep, pos, cap)
    rows = jnp.broadcast_to(xt[:, None, :], (T_my, k, d)).reshape(T_my * k, d)
    send = jnp.zeros((E, cap + 1, d), xt.dtype)
    send = send.at[flat_e, slot].set(rows)
    send = send[:, :cap].reshape(n_shards, e_loc, cap, d)
    meta = (flat_e, slot, keep, flat_g)
    return send, meta


def _local_combine(recv_back, meta, T_my: int, k: int, cap: int, dtype):
    """Inverse of dispatch: pull each assignment's expert output back out of
    the returned buffers and sum over the k experts per token."""
    flat_e, slot, keep, flat_g = meta
    E = recv_back.shape[0] * recv_back.shape[1]
    d = recv_back.shape[-1]
    flat_buf = recv_back.reshape(E, cap, d)
    picked = flat_buf[flat_e, jnp.clip(slot, 0, cap - 1)]
    picked = jnp.where(keep[:, None], picked, 0).astype(dtype)
    y = (picked * flat_g[:, None].astype(dtype)).reshape(T_my, k, d).sum(axis=1)
    return y


def moe_block_a2a(x: jax.Array, p: Dict, cfg: ModelConfig, mesh
                  ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (B, S, d), explicit-EP version of moe_block."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    T = B * S
    data_axes = tuple(a for a in mesh.axis_names if a != "model")
    n_model = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
    n_data = mesh.size // n_model
    e_loc = E // n_model
    T_my = T // mesh.size
    # per-source-shard capacity for each destination expert
    cap = max(8, int(math.ceil(T_my * k / E * cfg.capacity_factor / 8)) * 8)

    def body(xt, router, wi_g, wi_u, wo):
        # xt: (T_my, d); router: (d, E); wi/wo: (e_loc, ·, ·)
        logits = xt @ router
        send, meta = _local_dispatch(xt, logits, n_model, e_loc, cap, k)
        # exchange: rows grouped by destination shard -> by source shard
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=False)          # (n_model, e_loc, cap, d)
        buf = recv.reshape(e_loc, n_model * cap, d)
        g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, wi_g
                                   ).astype(jnp.float32)).astype(xt.dtype)
        u = jnp.einsum("ecd,edf->ecf", buf, wi_u)
        out = jnp.einsum("ecf,efd->ecd", g * u, wo)     # (e_loc, n_model*cap, d)
        back = out.reshape(e_loc, n_model, cap, d).transpose(1, 0, 2, 3)
        recv_back = jax.lax.all_to_all(back, "model", split_axis=0,
                                       concat_axis=0, tiled=False)
        y = _local_combine(recv_back, meta, T_my, k, cap, xt.dtype)
        # load-balance aux (local estimate, averaged over devices)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[meta[0]].add(1.0) / (T_my * k)
        aux = E * jnp.sum(me * ce)
        aux = jax.lax.pmean(aux, "model")
        for a in data_axes:
            aux = jax.lax.pmean(aux, a)
        return y, aux

    xt = x.reshape(T, d)
    batch_spec = P(data_axes + ("model",) if len(data_axes) > 1
                   else (data_axes[0], "model"))
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(batch_spec, P(), P("model"), P("model"), P("model")),
        out_specs=(batch_spec, P()),
        check_vma=False,
    )
    y, aux = fn(xt, p["router"], p["wi_gate"], p["wi_up"], p["wo"])
    return y.reshape(B, S, d), aux
