"""Blocked causal GQA flash attention (forward) — Pallas TPU kernel.

IO-aware attention (FlashAttention, arXiv:2205.14135) adapted to the TPU
memory hierarchy: (Bq, Dh) query tiles stay resident in VMEM while (Bk, Dh)
key/value tiles stream HBM->VMEM; the online-softmax running max/sum and
the output accumulator live in VMEM scratch across the kv grid dimension.
Supports:
  * GQA — the kv-head index is derived from the q-head index inside the
    BlockSpec index maps (no materialized head repeat),
  * causal masking,
  * optional sliding window (Gemma-3-style local layers).

Used by the LM family's train/prefill steps; decode uses the pure-jnp path
(one-token query tiles would waste the MXU).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, block_q: int, block_k: int, causal: bool,
                  window: int, kv_blocks: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    i = pl.program_id(1)
    q = q_ref[0]                       # (Bq, Dh)
    k = k_ref[0]                       # (Bk, Dh)
    v = v_ref[0]                       # (Bk, Dh)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), dtype=jnp.bool_)
    if causal:
        mask &= q_pos >= k_pos
    if window > 0:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]                                   # (Bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)               # (Bq, 1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                                  # (Bq, Bk)
    # fully-masked rows (e.g. causal rows before any kv) produce exp(-inf
    # - -inf) garbage; zero them explicitly.
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
    alpha = jnp.where(m_prev > NEG_INF / 2, jnp.exp(m_prev - m_new), 0.0)

    l_new = alpha * l_ref[:, :1] + p.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == kv_blocks - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: int = 0, block_q: int = 128,
                    block_k: int = 128, interpret: bool = True) -> jnp.ndarray:
    """q: (B, Hq, Sq, Dh); k, v: (B, Hkv, Skv, Dh); Hq % Hkv == 0.

    Returns (B, Hq, Sq, Dh) in q's dtype. window > 0 keeps only keys with
    q_pos - k_pos in [0, window).
    """
    b, hq, sq, dh = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0 and k.shape == v.shape
    group = hq // hkv
    scale = dh ** -0.5

    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    assert sq % block_q == 0 and skv % block_k == 0
    q_blocks, kv_blocks = sq // block_q, skv // block_k

    qf = q.reshape(b * hq, sq, dh)
    kf = k.reshape(b * hkv, skv, dh)
    vf = v.reshape(b * hkv, skv, dh)

    def kv_head(h):  # flattened q-head -> flattened kv-head
        return (h // hq) * hkv + (h % hq) // group

    kernel = functools.partial(
        _flash_kernel, scale=scale, block_q=block_q, block_k=block_k,
        causal=causal, window=window, kv_blocks=kv_blocks)

    out = pl.pallas_call(
        kernel,
        grid=(b * hq, q_blocks, kv_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, i, j: (kv_head(h), j, 0)),
            pl.BlockSpec((1, block_k, dh), lambda h, i, j: (kv_head(h), j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, dh), lambda h, i, j: (h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, dh), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, dh)
