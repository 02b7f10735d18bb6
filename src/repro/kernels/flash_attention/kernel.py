"""Pallas TPU flash attention (causal, online softmax).

Grid: (B*K, G, nq, nk) with the kv axis innermost (sequential revisiting).
Running max / denominator / accumulator live in VMEM scratch and persist
across the nk steps of one (bh, g, qi) cell; the output block is written on
the last visited kv step.  Out-of-triangle kv blocks are skipped with
``pl.when`` so no MXU work is issued for them (the same triangular schedule
the jnp ``chunked_causal_attention`` stand-in uses, which keeps the dry-run
FLOP accounting consistent with this kernel).

Block shapes: (bq, d) x (bk, d) with bq/bk multiples of 128 to keep the MXU
fed (d=64 archs underfill lanes; noted in DESIGN.md).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 128
NEG_INF = -1e30


def _make_kernel(scale: float, nk: int, bq: int, bk: int):
    def kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        qi = pl.program_id(2)
        kj = pl.program_id(3)

        @pl.when(kj == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        # causal skip: a kv block strictly after the q block contributes nothing
        @pl.when(kj * bk <= qi * bq + bq - 1)
        def _compute():
            q = q_ref[0, 0].astype(jnp.float32) * scale      # (bq, d)
            k = k_ref[0, 0].astype(jnp.float32)              # (bk, d)
            v = v_ref[0, 0].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            kpos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        @pl.when(kj == nk - 1)
        def _finalize():
            o_ref[0, 0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("bq", "bk", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                    interpret: bool = False) -> jax.Array:
    """q: (B, S, H, d); k, v: (B, S, K, d).  Causal.  Returns (B, S, H, d).

    Layout: q regrouped to (B*K, G, S, d) so one grid cell reads one kv-head
    block shared by its G query heads (GQA-native tiling)."""
    B, S, H, d = q.shape
    K = k.shape[2]
    G = H // K
    bq = min(bq, S)
    bk = min(bk, S)
    assert S % bq == 0 and S % bk == 0
    nq, nk = S // bq, S // bk
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(B, S, K, G, d).transpose(0, 2, 3, 1, 4).reshape(B * K, G, S, d)
    kg = k.transpose(0, 2, 1, 3).reshape(B * K, 1, S, d)
    vg = v.transpose(0, 2, 1, 3).reshape(B * K, 1, S, d)

    out = pl.pallas_call(
        _make_kernel(scale, nk, bq, bk),
        grid=(B * K, G, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda bh, g, qi, kj: (bh, g, qi, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bh, g, qi, kj: (bh, 0, kj, 0)),
            pl.BlockSpec((1, 1, bk, d), lambda bh, g, qi, kj: (bh, 0, kj, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bh, g, qi, kj: (bh, g, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K, G, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qg, kg, vg)
    out = out.reshape(B, K, G, S, d).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, S, H, d)
