"""Pallas TPU kernel: blocked online-softmax (flash) attention.

Supports GQA (query-head groups sharing one KV head), causal masking, sliding
windows (mistral / recurrentgemma local attention) and a query-position offset
(so the same kernel serves prefill chunks and decode with a long KV cache).

Grid: (B * Hq, q_tiles, kv_tiles) — kv innermost/sequential; running (m, l, acc)
live in VMEM scratch.  MXU work per grid step is a (bq x D) @ (D x bk) and a
(bq x bk) @ (bk x D) matmul; block defaults (bq=bk=128, D<=256) keep the
working set ~ (2*128*D + 128*128) * 4B « VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_pallas"]

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
            scale: float, causal: bool, window: int | None,
            q_offset: int, kv_len: int, block_q: int, block_k: int,
            kv_tiles: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    v = v_ref[0]  # (bk, d)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32) * scale  # (bq, bk)

    iq = pl.program_id(1)
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = k_pos < kv_len  # exclude zero-padded keys
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_sc[...]           # (bq, 1)
    l_prev = l_sc[...]           # (bq, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    # explicit re-mask: fully-masked rows would otherwise get exp(-inf+inf)=1
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)  # (bq, bk)
    correction = jnp.exp(m_prev - m_new)
    l_new = l_prev * correction + jnp.sum(p, axis=1, keepdims=True)
    acc = acc_sc[...] * correction + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_sc[...] = m_new
    l_sc[...] = l_new
    acc_sc[...] = acc

    @pl.when(ik == kv_tiles - 1)
    def _finish():
        # Fully-masked rows (e.g. q rows before any valid key) get l == 0;
        # emit zeros rather than NaNs.
        l = l_sc[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_sc[...] / safe).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           window: int | None = None, q_offset: int = 0,
                           kv_len: int | None = None,
                           scale: float | None = None,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """q: (B, Hq, Sq, D);  k, v: (B, Hkv, Skv, D);  Hq % Hkv == 0 (GQA).

    Returns (B, Hq, Sq, D) in q.dtype.  Sq % block_q == 0, Skv % block_k == 0
    (caller pads — see ops.py).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0
    group = hq // hkv
    if scale is None:
        scale = d ** -0.5
    assert sq % block_q == 0 and skv % block_k == 0, (sq, skv, block_q, block_k)
    q_tiles, kv_tiles = sq // block_q, skv // block_k
    grid = (b * hq, q_tiles, kv_tiles)

    # Collapse (b, h) into block index dim 0 for in-kernel simplicity.
    q_r = q.reshape(b * hq, sq, d)
    k_r = k.reshape(b * hkv, skv, d)
    v_r = v.reshape(b * hkv, skv, d)
    q_spec = pl.BlockSpec((1, block_q, d), lambda ibh, iq, ik: (ibh, iq, 0))
    kv_spec = pl.BlockSpec((1, block_k, d),
                           lambda ibh, iq, ik: ((ibh // hq) * hkv + (ibh % hq) // group, ik, 0))
    out_shape = jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype)

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, q_offset=q_offset,
        kv_len=skv if kv_len is None else kv_len,
        block_q=block_q, block_k=block_k, kv_tiles=kv_tiles)
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        **kw,
    )(q_r, k_r, v_r)
    return out.reshape(b, hq, sq, d)
