"""Pallas TPU kernel: chunked diagonal (complex-pair) linear recurrence.

The paper's O(N) reservoir step as a TPU kernel.  Complex state is realified
into separate (re, im) f32 lane arrays (TPU VPU has no complex dtype —
Appendix A's memory-view trick becomes two lanes + a 2x2 rotation).

Grid layout: (batch_tiles, state_tiles, time_chunks), time innermost and
*sequential* ("arbitrary" dimension semantics): the carry lives in VMEM scratch
and persists across time-chunk grid steps, so the state never round-trips to
HBM inside a (batch, state) tile — per-chunk HBM traffic is exactly the
inputs/outputs (the TPU-native meaning of "the update is O(N)").

Block shapes default to (8 batch, 256 time, 128 state) — the state tile matches
the 128-wide VPU lanes and the f32 VMEM budget is
   (bb*bt*bn) * 4 arrays * 4B = 8*256*128*16B = 4 MiB  « 128 MiB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["diag_scan_pallas_raw", "decode_fused_pallas_raw", "SLOT_TILE"]

#: Slots per grid step of the fused decode: one f32 sublane tile.
SLOT_TILE = 8


def _check_lanes(dtype) -> None:
    """Mosaic kernels work in 32-bit and narrower lanes.  A 64-bit operand
    on the TPU is a configuration error (x64 left on for a device run), and
    rounding it silently would serve below the configured precision."""
    if jnp.dtype(dtype).itemsize > 4:
        raise TypeError(
            f"TPU Pallas kernels take 32-bit lanes, got {jnp.dtype(dtype)}: "
            f"the device path runs in float32 (x64 is for CPU references)")


def _kernel(h0_re_ref, h0_im_ref, a_re_ref, a_im_ref, x_re_ref, x_im_ref,
            o_re_ref, o_im_ref, carry_re, carry_im, *, block_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_re[...] = h0_re_ref[...]
        carry_im[...] = h0_im_ref[...]

    def body(t, carry):
        hr, hi = carry
        ar = a_re_ref[:, t, :]
        ai = a_im_ref[:, t, :]
        xr = x_re_ref[:, t, :]
        xi = x_im_ref[:, t, :]
        # Complex multiply on (re, im) lanes + accumulate input.
        new_r = ar * hr - ai * hi + xr
        new_i = ar * hi + ai * hr + xi
        o_re_ref[:, t, :] = new_r
        o_im_ref[:, t, :] = new_i
        return new_r, new_i

    hr, hi = jax.lax.fori_loop(
        0, block_t, body, (carry_re[...], carry_im[...]))
    carry_re[...] = hr
    carry_im[...] = hi


def diag_scan_pallas_raw(a_re, a_im, x_re, x_im, h0_re, h0_im, *,
                         block_b: int = 8, block_t: int = 256,
                         block_n: int = 128, interpret: bool | None = None):
    """h_t = a_t * h_{t-1} + x_t on realified complex lanes.

    All of a_*, x_*: (B, T, N) f32/f64; h0_*: (B, N).  Returns (h_re, h_im)
    with shape (B, T, N).  Caller handles broadcasting/padding (see ops.py).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, t, n = x_re.shape
    assert b % block_b == 0 and t % block_t == 0 and n % block_n == 0, (
        (b, t, n), (block_b, block_t, block_n))
    grid = (b // block_b, n // block_n, t // block_t)

    def xmap(ib, in_, it):
        return (ib, it, in_)

    def hmap(ib, in_, it):
        return (ib, in_)

    x_spec = pl.BlockSpec((block_b, block_t, block_n), xmap)
    h_spec = pl.BlockSpec((block_b, block_n), hmap)
    out_shape = [jax.ShapeDtypeStruct((b, t, n), x_re.dtype)] * 2

    kernel = functools.partial(_kernel, block_t=block_t)
    kw = {}
    if not interpret:
        _check_lanes(x_re.dtype)
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    o_re, o_im = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[h_spec, h_spec, x_spec, x_spec, x_spec, x_spec],
        out_specs=[x_spec, x_spec],
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_b, block_n), x_re.dtype),
            pltpu.VMEM((block_b, block_n), x_re.dtype),
        ],
        interpret=interpret,
        **kw,
    )(h0_re, h0_im, a_re, a_im, x_re, x_im)
    return o_re, o_im


# --------------------------------------------------------------------------- #
# Fused multi-token closed-loop decode                                         #
# --------------------------------------------------------------------------- #
def _mm(v, w):
    """Row block ``(TB, F)`` times a shared ``(F, G)`` or per-slot
    ``(TB, F, G)`` weight.  Broadcast-reduce instead of dot_general: B and D
    are decode-sized, the VPU handles it in exact f32."""
    if w.ndim == 2:
        w = w[None]
    return jnp.sum(v[:, :, None] * w, axis=1)


def _decode_kernel(a_re_ref, a_im_ref, h0_re_ref, h0_im_ref, y0_ref,
                   wd_re_ref, wd_im_ref, wy_ref, b_out_ref, wh_re_ref,
                   wh_im_ref, m_ref, o_h_re_ref, o_h_im_ref, o_y_ref,
                   o_ys_ref, *, k: int, ensemble: str):
    a_re = a_re_ref[...]                 # (1 | TB, NC)
    a_im = a_im_ref[...]
    wd_re = wd_re_ref[...]               # (D, NC) shared | (TB, D, NC)
    wd_im = wd_im_ref[...]
    wy = wy_ref[...]                     # (D, D) | (TB, D, D)
    b_out = b_out_ref[...]               # (1 | TB, D)
    wh_re = wh_re_ref[...]               # (NC, D) | (TB, NC, D)
    wh_im = wh_im_ref[...]
    m = m_ref[...][:, :1]                # (TB, 1) float occupancy mask
    live = m > 0.5
    denom = jnp.maximum(jnp.sum(m), 1.0)

    def body(t, carry):
        hr, hi, y = carry
        # Drive from the fed-back output (u == y in closed loop; the caller
        # pre-summed W_in + W_fb into wd).
        nhr = a_re * hr - a_im * hi + _mm(y, wd_re)
        nhi = a_re * hi + a_im * hr + _mm(y, wd_im)
        hr = jnp.where(live, nhr, hr)
        hi = jnp.where(live, nhi, hi)
        # Readout on the NEW state, feedback column from the carried y —
        # identical ordering to arena.closed_loop's assemble_features.
        y_new = b_out + _mm(y, wy) + _mm(hr, wh_re) + _mm(hi, wh_im)
        if ensemble == "mean":
            y_new = jnp.broadcast_to(
                jnp.sum(y_new * m, axis=0, keepdims=True) / denom,
                y_new.shape)
        y_new = jnp.where(live, y_new, y)
        o_ys_ref[t, :, :] = y_new
        return hr, hi, y_new

    hr, hi, y = jax.lax.fori_loop(
        0, k, body, (h0_re_ref[...], h0_im_ref[...], y0_ref[...]))
    o_h_re_ref[...] = hr
    o_h_im_ref[...] = hi
    o_y_ref[...] = y


def decode_fused_pallas_raw(a_re, a_im, h0_re, h0_im, y0, wd_re, wd_im, wy,
                            b_out, wh_re, wh_im, m, *, k: int,
                            ensemble: str = "off",
                            interpret: bool | None = None):
    """K closed-loop decode steps in ONE dispatch: diag step + readout matmul
    + ensemble reduce + feedback write, carry resident on-device.

    Realified-lane operands (ops.py pads): ``h0_*`` (B, NC), ``y0`` (B, D)
    and ``m`` (B, LANES) replicated float mask are per slot.  ``a_*`` and
    ``b_out`` are (1, ·) when shared by every slot or (B, ·) per slot;
    ``wd_*`` (D, NC), ``wy`` (D, D) and ``wh_*`` (NC, D) are 2D when shared
    or carry a leading B per slot (param batch / readout pool).

    The grid runs in parallel over tiles of :data:`SLOT_TILE` slots.  Per-slot
    operands are slot-tiled; a shared operand is one block with a constant
    index map, fetched once and reused by every tile.  ``ensemble="mean"``
    couples every slot at every step, so it runs as one tile.
    Returns ``(h_re, h_im, y, ys)`` with ``ys`` (K, B, D).
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, d = y0.shape
    block_b = b if ensemble == "mean" else SLOT_TILE
    assert b % block_b == 0, (b, block_b)

    # Constant block indices are ``i * 0``: a literal 0 would be an int64
    # index under x64, which Mosaic refuses.
    def spec(x, per_slot: bool):
        nd = x.ndim
        if per_slot:
            return pl.BlockSpec((block_b,) + x.shape[1:],
                                lambda i: (i,) + (i * 0,) * (nd - 1))
        return pl.BlockSpec(x.shape, lambda i: (i * 0,) * nd)

    in_specs = [spec(a_re, a_re.shape[0] == b), spec(a_im, a_im.shape[0] == b),
                spec(h0_re, True), spec(h0_im, True), spec(y0, True),
                spec(wd_re, wd_re.ndim == 3), spec(wd_im, wd_im.ndim == 3),
                spec(wy, wy.ndim == 3), spec(b_out, b_out.shape[0] == b),
                spec(wh_re, wh_re.ndim == 3), spec(wh_im, wh_im.ndim == 3),
                spec(m, True)]
    out_shape = [
        jax.ShapeDtypeStruct(h0_re.shape, h0_re.dtype),
        jax.ShapeDtypeStruct(h0_im.shape, h0_im.dtype),
        jax.ShapeDtypeStruct((b, d), y0.dtype),
        jax.ShapeDtypeStruct((k, b, d), y0.dtype),
    ]
    out_specs = [spec(h0_re, True), spec(h0_im, True), spec(y0, True),
                 pl.BlockSpec((k, block_b, d), lambda i: (i * 0, i, i * 0))]
    kw = {}
    if not interpret:
        _check_lanes(y0.dtype)
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel",))
    kernel = functools.partial(_decode_kernel, k=k, ensemble=ensemble)
    return pl.pallas_call(
        kernel, grid=(b // block_b,), in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, interpret=interpret, **kw)(
        a_re, a_im, h0_re, h0_im, y0, wd_re, wd_im, wy, b_out, wh_re,
        wh_im, m)
