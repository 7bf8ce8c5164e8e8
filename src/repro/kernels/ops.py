"""Jit'd public wrappers around the Pallas kernels.

* ``diag_scan`` — padding/broadcast + realify + custom VJP (the backward of a
  diagonal recurrence is the same recurrence run in reverse with conjugated,
  shifted coefficients — so the kernel serves its own gradient).
* ``flash_attention`` — padding + GQA plumbing; backward falls back to
  recompute-with-the-jnp-oracle (standard flash recompute strategy; the
  forward hot-spot is the kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import ref as ref_mod
from .diag_scan import (SLOT_TILE, decode_fused_pallas_raw,
                        diag_scan_pallas_raw)
from .flash_attention import flash_attention_pallas

__all__ = ["diag_scan", "decode_fused", "flash_attention"]


def _round_up(x, m):
    return (x + m - 1) // m * m


def _data_shards() -> int:
    """Devices on the ``data`` axis of the mesh in context, 1 without one.
    The batch / slot axis of every kernel operand rides that axis
    (``sharding.rules.plan_arena``)."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or "data" not in mesh.axis_names:
        return 1
    return mesh.shape["data"]


def _per_device(kernel, in_specs, out_specs, *args):
    """Mosaic kernels are not partitioned automatically.  With a mesh in
    context, run ``kernel`` under ``shard_map``: each device on its own
    block of rows (``P("data")``) and a whole copy of every ``P()``
    operand."""
    if _data_shards() == 1:
        return kernel(*args)
    return jax.shard_map(kernel, in_specs=in_specs, out_specs=out_specs,
                         check_vma=False)(*args)


def diag_scan(a, x, h0=None, *, block_b: int = 8, block_t: int = 256,
              block_n: int = 128, interpret: bool | None = None):
    """h_t = a_t h_{t-1} + x_t on TPU via the Pallas kernel.

    a: (N,) / (T, N) / (B, T, N), real or complex; x: (B, T, N).
    Returns all states (B, T, N) in the promoted dtype.  Differentiable in
    (a, x, h0).
    """
    b, t, n = x.shape
    out_dtype = jnp.result_type(a.dtype, x.dtype)
    if h0 is None:
        h0 = jnp.zeros((b, n), out_dtype)
    return _diag_scan_vjp(a, x, jnp.broadcast_to(h0, (b, n)).astype(out_dtype),
                          block_b, block_t, block_n, interpret)


def _split(z, real_dtype):
    if jnp.iscomplexobj(z):
        return z.real.astype(real_dtype), z.imag.astype(real_dtype)
    return z.astype(real_dtype), jnp.zeros_like(z, real_dtype)


def _scan_padded(a_full, x, h0, block_b, block_t, block_n, interpret):
    b, t, n = x.shape
    out_dtype = jnp.result_type(a_full.dtype, x.dtype)
    is_cpx = jnp.issubdtype(out_dtype, jnp.complexfloating)
    real_dtype = jnp.float64 if out_dtype in (jnp.complex128, jnp.float64) \
        else jnp.float32
    a_re, a_im = _split(a_full, real_dtype)
    x_re, x_im = _split(x, real_dtype)
    h_re, h_im = _split(h0, real_dtype)
    bp = _round_up(b, block_b * _data_shards())
    tp, np_ = _round_up(t, block_t), _round_up(n, block_n)
    pad = ((0, bp - b), (0, tp - t), (0, np_ - n))
    hpad = ((0, bp - b), (0, np_ - n))
    args = [jnp.pad(v, pad) for v in (a_re, a_im, x_re, x_im)]
    h0s = [jnp.pad(v, hpad) for v in (h_re, h_im)]
    rows = P("data")
    o_re, o_im = _per_device(
        functools.partial(diag_scan_pallas_raw, block_b=block_b,
                          block_t=block_t, block_n=block_n,
                          interpret=interpret),
        (rows,) * 6, (rows, rows), *args, *h0s)
    o_re, o_im = o_re[:b, :t, :n], o_im[:b, :t, :n]
    if is_cpx:
        return jax.lax.complex(o_re, o_im).astype(out_dtype)
    return o_re.astype(out_dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _diag_scan_vjp(a, x, h0, block_b, block_t, block_n, interpret):
    return _fwd(a, x, h0, block_b, block_t, block_n, interpret)[0]


def _fwd(a, x, h0, block_b, block_t, block_n, interpret):
    b, t, n = x.shape
    a_full = jnp.broadcast_to(a, (b, t, n))
    out = _scan_padded(a_full, x, h0, block_b, block_t, block_n, interpret)
    return out, (a, h0, out)


def _bwd(block_b, block_t, block_n, interpret, res, g):
    a, h0, h = res
    b, t, n = g.shape
    a_full = jnp.broadcast_to(a, (b, t, n))
    # s_t = g_t + a_{t+1} s_{t+1}  — forward scan on flipped arrays with
    # right-shifted coefficients.  (JAX's holomorphic-VJP convention carries NO
    # conjugation: vjp of y = a*x is (a*g, x*g) — verified against autodiff.)
    a_f = jnp.flip(a_full, axis=1)
    coeff = jnp.concatenate([jnp.zeros_like(a_f[:, :1]), a_f[:, :-1]], axis=1)
    g_f = jnp.flip(g, axis=1)
    h0z = jnp.zeros_like(h0)
    s_f = _scan_padded(coeff, g_f.astype(h.dtype), h0z, block_b, block_t,
                       block_n, interpret)
    s = jnp.flip(s_f, axis=1)
    dx = s.astype(g.dtype)
    # da_t = s_t * h_{t-1};  h_{-1} = h0.
    h_prev = jnp.concatenate([h0[:, None], h[:, :-1]], axis=1)
    da_full = s * h_prev
    if a.ndim == 1:
        da = da_full.sum(axis=(0, 1))
    elif a.ndim == 2:
        da = da_full.sum(axis=0)
    else:
        da = da_full
    if not jnp.iscomplexobj(a):
        da = da.real
    dh0 = a_full[:, 0] * s[:, 0]
    if not jnp.iscomplexobj(h0):
        dh0 = dh0.real
    return da.astype(a.dtype), dx, dh0.astype(h0.dtype)


_diag_scan_vjp.defvjp(_fwd, _bwd)


# --------------------------------------------------------------------------- #
# Fused multi-token decode wrapper                                             #
# --------------------------------------------------------------------------- #
def decode_fused(a_re, a_im, h_re, h_im, y0, wd_re, wd_im, wy, b_out, wh_re,
                 wh_im, mask, *, k: int, ensemble: str = "off",
                 interpret: bool | None = None):
    """K-token fused closed-loop decode through the Pallas kernel.

    Accepts the same shared-or-batched realified-lane operands as
    ``ref.decode_fused_ref`` and pads them (B -> sublane, NC/D -> lane
    multiples) before the kernel call.  Shared operands stay shared — the
    kernel reads them as one block for every slot tile — so only per-slot
    operands grow with B.  All padding is inert: padded slots carry a zero
    mask (frozen zero rows, excluded from the ensemble mean) and padded
    lanes carry zero weights.
    """
    b, nc = h_re.shape
    d = y0.shape[-1]
    # The mean ensemble couples every slot at every step: each device then
    # runs the whole (replicated) block.
    shards = 1 if ensemble == "mean" else _data_shards()
    bp = _round_up(b, SLOT_TILE * shards)
    ncp, dp = _round_up(nc, 128), _round_up(d, 128)
    pb, pn, pd = (0, bp - b), (0, ncp - nc), (0, dp - d)

    def rows(v, lanes):
        """(X,) shared -> (1, X'); (B, X) per slot -> (B', X')."""
        return jnp.pad(v[None] if v.ndim == 1 else v,
                       ((0, 0) if v.ndim == 1 else pb, lanes))

    def mat(w, r, c):
        """(R, C) shared or (B, R, C) per slot, padded to (R', C')."""
        return jnp.pad(w, (r, c) if w.ndim == 2 else (pb, r, c))

    args = (rows(a_re, pn), rows(a_im, pn),
            jnp.pad(h_re, (pb, pn)), jnp.pad(h_im, (pb, pn)),
            jnp.pad(y0, (pb, pd)),
            mat(wd_re, pd, pn), mat(wd_im, pd, pn), mat(wy, pd, pd),
            rows(b_out, pd), mat(wh_re, pn, pd), mat(wh_im, pn, pd))
    m = jnp.pad(jnp.broadcast_to(
        jnp.asarray(mask, y0.dtype)[:, None], (b, 128)), (pb, (0, 0)))
    # Per-slot operands are split by rows over the devices; shared operands
    # go whole to every device.
    row = P() if ensemble == "mean" else P("data")
    per_slot = (a_re.ndim == 2, a_im.ndim == 2, True, True, True,
                wd_re.ndim == 3, wd_im.ndim == 3, wy.ndim == 3,
                b_out.ndim == 2, wh_re.ndim == 3, wh_im.ndim == 3, True)
    o_re, o_im, y, ys = _per_device(
        functools.partial(decode_fused_pallas_raw, k=k, ensemble=ensemble,
                          interpret=interpret),
        tuple(row if s else P() for s in per_slot),
        (row, row, row, P(None, *row)), *args, m)
    return o_re[:b, :nc], o_im[:b, :nc], y[:b, :d], ys[:, :b, :d]


# --------------------------------------------------------------------------- #
# Flash attention wrapper                                                      #
# --------------------------------------------------------------------------- #
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal=True, window=None, q_offset=0,
                    block_q=128, block_k=128, interpret=None):
    """Blocked online-softmax attention (GQA/causal/window), padded as needed."""
    return _fa_fwd(q, k, v, causal, window, q_offset, block_q, block_k,
                   interpret)[0]


def _fa_pad_call(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    b, hq, sq, d = q.shape
    skv = k.shape[2]
    sqp, skvp = _round_up(sq, block_q), _round_up(skv, block_k)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skvp - skv), (0, 0)))
    out = flash_attention_pallas(
        qp, kp, vp, causal=causal, window=window, q_offset=q_offset,
        kv_len=skv, scale=d ** -0.5, block_q=block_q, block_k=block_k,
        interpret=interpret,
    )
    return out[:, :, :sq]


def _fa_fwd(q, k, v, causal, window, q_offset, block_q, block_k, interpret):
    out = _fa_pad_call(q, k, v, causal, window, q_offset, block_q, block_k,
                       interpret)
    return out, (q, k, v)


def _fa_bwd(causal, window, q_offset, block_q, block_k, interpret, res, g):
    q, k, v = res

    # Recompute-based backward through the jnp oracle (flash recompute).
    def f(q, k, v):
        return ref_mod.attention_ref(q, k, v, causal=causal, window=window,
                                     q_offset=q_offset)

    _, vjp = jax.vjp(f, q, k, v)
    return vjp(g)


flash_attention.defvjp(_fa_fwd, _fa_bwd)
