"""Pallas TPU kernel: flash attention (online-softmax, causal-masked).

This is the standing §Perf lever for every memory-dominant LM cell in the
roofline table: the jnp chunked attention materializes each (q_blk x k_blk)
score block to HBM between ops (the perfect-fusion floor counts exactly
that traffic); this kernel keeps the block, the running max/denominator
and the output accumulator resident in VMEM — HBM traffic collapses to
q/k/v reads + one o write.

Grid (B*H, n_q, n_k) with the k axis innermost ("arbitrary": it revisits
the same output block); accumulators live in VMEM scratch across k steps.
GQA is folded in the wrapper (kv heads repeated to q heads).  Validated
in interpret mode against the pure-jnp oracle (tests/test_flash_attention).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
NEG_INF = -1e30


def _flash_kernel(causal: bool, scale: float, kblk: int, nk: int,
                  q_ref, k_ref, v_ref, o_ref, acc, m_s, l_s):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)

    q = q_ref[0]                                   # (qblk, Dh)
    k = k_ref[0]                                   # (kblk, Dh)
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=F32) * scale  # (qblk,kblk)
    if causal:
        iq = pl.program_id(1)
        qblk = q.shape[0]
        qpos = iq * qblk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = ik * kblk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

    m_prev = m_s[...]                              # (qblk, 1)
    l_prev = l_s[...]
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)                # rescale of old state
    p = jnp.exp(s - m_new)                         # (qblk, kblk)
    l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * alpha + jax.lax.dot(
        p.astype(v.dtype), v, preferred_element_type=F32)
    m_s[...] = m_new
    l_s[...] = l_new

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0] = (acc[...] / jnp.maximum(l_s[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, causal: bool = True, q_block: int = 256,
                       kv_block: int = 256, interpret: bool = False):
    """Core kernel on folded heads.  q: (BH, Sq, Dh); k, v: (BH, Skv, Dh);
    Sq % q_block == 0 and Skv % kv_block == 0 (wrapper pads)."""
    BH, Sq, Dh = q.shape
    Skv = k.shape[1]
    assert Sq % q_block == 0 and Skv % kv_block == 0
    nq, nk = Sq // q_block, Skv // kv_block
    scale = 1.0 / (Dh ** 0.5)
    grid = (BH, nq, nk)
    kern = functools.partial(_flash_kernel, causal, scale, kv_block, nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, q_block, Dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, kv_block, Dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, kv_block, Dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, q_block, Dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, Dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((q_block, Dh), F32),        # output accumulator
            pltpu.VMEM((q_block, 1), F32),         # running max
            pltpu.VMEM((q_block, 1), F32),         # running denominator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, q_block: int = 256,
                    kv_block: int = 256, interpret: bool = False):
    """GQA wrapper.  q: (B, Sq, Hq, Dh); k, v: (B, Skv, Hkv, Dh) ->
    (B, Sq, Hq, Dh).  Pads sequences to block multiples (padded kv rows are
    masked by construction for causal; for non-causal they are masked via
    a -inf score contribution of zero keys... hence wrapper requires exact
    tiling for non-causal and pads only q)."""
    B, Sq, Hq, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    # fold heads
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(B * Hq, x.shape[1], Dh)
    qf, kf, vf = fold(q), fold(k), fold(v)
    qb = min(q_block, Sq)
    while Sq % qb:
        qb -= 1
    kb = min(kv_block, Skv)
    while Skv % kb:
        kb -= 1
    o = flash_attention_bh(qf, kf, vf, causal=causal, q_block=qb,
                           kv_block=kb, interpret=interpret)
    return o.reshape(B, Hq, Sq, Dh).transpose(0, 2, 1, 3)


def flash_attention_ref(q, k, v, *, causal: bool = True):
    """Pure-jnp oracle (full-score softmax attention with GQA)."""
    B, Sq, Hq, Dh = q.shape
    Skv, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if G > 1:
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(F32), k.astype(F32))
    s = s / (Dh ** 0.5)
    if causal:
        mask = jnp.arange(Sq)[:, None] >= jnp.arange(Skv)[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(F32))
    return o.astype(q.dtype)
