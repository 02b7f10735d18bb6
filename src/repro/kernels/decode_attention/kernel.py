"""Pallas TPU flash-decode: one query token against a long KV cache.

Decode is memory-bound (the whole cache streams HBM->VMEM once per token);
the kernel tiles the sequence axis, keeps online-softmax running stats in
VMEM scratch, and masks the tail beyond ``length``.  Grid: (B*K, ns) with the
sequence axis innermost/sequential.  The G query heads of one kv head are
processed together as an (G, d) x (d, bs) MXU matmul.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 512
NEG_INF = -1e30


def _make_kernel(scale: float, ns: int, bs: int):
    def kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr):
        sj = pl.program_id(1)

        @pl.when(sj == 0)
        def _init():
            m_scr[...] = jnp.full_like(m_scr, NEG_INF)
            l_scr[...] = jnp.zeros_like(l_scr)
            acc_scr[...] = jnp.zeros_like(acc_scr)

        length = len_ref[0]

        @pl.when(sj * bs < length)
        def _compute():
            q = q_ref[0].astype(jnp.float32) * scale          # (G, d)
            k = k_ref[0].astype(jnp.float32)                  # (bs, d)
            v = v_ref[0].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)  # (G, bs)
            pos = sj * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(pos < length, s, NEG_INF)
            m_prev = m_scr[...]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
            acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
                p, v, preferred_element_type=jnp.float32)
            m_scr[...] = m_new

        @pl.when(sj == ns - 1)
        def _finalize():
            o_ref[0] = (acc_scr[...] / l_scr[...]).astype(o_ref.dtype)

    return kernel


@functools.partial(jax.jit, static_argnames=("bs", "interpret"))
def decode_attention(q, k_cache, v_cache, length, bs: int = DEFAULT_BS,
                     interpret: bool = False):
    """q: (B, H, d); caches: (B, S, K, d); length: () int32."""
    B, H, d = q.shape
    S = k_cache.shape[1]
    K = k_cache.shape[2]
    G = H // K
    bs = min(bs, S)
    assert S % bs == 0
    ns = S // bs
    scale = 1.0 / math.sqrt(d)
    qg = q.reshape(B, K, G, d).reshape(B * K, G, d)
    kg = k_cache.transpose(0, 2, 1, 3).reshape(B * K, S, d)
    vg = v_cache.transpose(0, 2, 1, 3).reshape(B * K, S, d)
    lengths = jnp.broadcast_to(length, (1,)).astype(jnp.int32)

    out = pl.pallas_call(
        _make_kernel(scale, ns, bs),
        grid=(B * K, ns),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, G, d), lambda bh, sj: (bh, 0, 0)),
            pl.BlockSpec((1, bs, d), lambda bh, sj: (bh, sj, 0)),
            pl.BlockSpec((1, bs, d), lambda bh, sj: (bh, sj, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, d), lambda bh, sj: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * K, G, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(lengths, qg, kg, vg)
    return out.reshape(B, H, d)
