"""Backend auto-dispatch for the diagonal reservoir scan.

One place that decides *how* the O(N) recurrence h_t = Lambda (.) h_{t-1} + x_t
is executed, from the shape of the work — instead of hard-coded ``method=``
strings scattered across callers:

* **decode / short prefill** (small T)  -> ``sequential``: lax.scan has the
  lowest per-step constant and no fix-up passes; at T ~ O(1) everything else
  is pure overhead.
* **long prefill on TPU**               -> ``pallas``: the chunked VMEM-carry
  kernel (``kernels.diag_scan_pallas_raw`` via ``kernels.ops.diag_scan``) —
  per-chunk HBM traffic is exactly the inputs/outputs.
* **long prefill elsewhere**            -> ``chunked``: the work-efficient
  two-pass scan that mirrors the kernel schedule.
* **mid-size T**                        -> ``associative`` fallback: O(log T)
  depth without the chunk bookkeeping, best when T is too short to amortize
  chunk fix-ups but too long for a serial scan.

Closed-loop decode has its own funnel: ``run_decode_fused`` executes K
feedback steps per dispatch (diag step + readout + ensemble reduce + feedback
write) through the fused Pallas kernel on TPU and the jnp reference
(``kernels.ref.decode_fused_ref``) everywhere else —
``resolve_decode_method`` picks between them.

All entry points take Q-basis (Appendix-A realified) operands; ``run_scan_q``
is the single execution funnel used by the ``core.esn`` pure functions and
``serve.engine.ReservoirEngine``.  This module lives in ``core`` (it depends
only on ``core.scan`` + ``kernels``) and is imported directly — the old
``serve.dispatch`` re-export shim is gone.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import scan as scan_mod
from ..kernels import ops as kernel_ops
from ..kernels import ref as kernel_refs

__all__ = [
    "SEQUENTIAL_MAX_T",
    "PALLAS_MIN_T",
    "resolve_method",
    "run_scan_q",
    "resolve_decode_method",
    "run_decode_fused",
    "device_fn",
]


def device_fn(fn, mesh=None):
    """``fn`` traced the way every jitted device function of the engine is.

    * Full-precision dots: on the TPU a float32 dot at default precision
      runs as one bfloat16 pass, below the float32 the serving path states.
      The CPU computes float32/float64 dots in full either way.
    * With ``mesh`` (the arena's device mesh) in context, so the Pallas
      wrappers run per device over the slot axis (``kernels.ops``): Mosaic
      kernels are not partitioned automatically.
    """
    @functools.wraps(fn)
    def traced(*args, **kwargs):
        with jax.default_matmul_precision("highest"):
            if mesh is None:
                return fn(*args, **kwargs)
            with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
                return fn(*args, **kwargs)
    # Executables and profiler events carry the wrapped function's name.
    traced.__name__ = getattr(fn, "__name__", None) or fn.func.__name__
    return traced


# Thresholds in steps along the time axis.  Calibrated coarsely: the
# crossover constants differ per backend, but the *ordering* of regimes does
# not, and every method computes identical numerics — a wrong guess costs
# time, never correctness.
SEQUENTIAL_MAX_T = 32     # decode & short prefill: serial scan wins
PALLAS_MIN_T = 512        # long prefill on TPU: the Pallas kernel


def resolve_method(t: int, *, backend: Optional[str] = None,
                   chunk: int = 128) -> str:
    """Pick a scan backend from the time extent of the work.

    ``t``: steps along time; ``backend``: jax platform ("tpu"/"cpu"/"gpu"),
    auto-detected when None; ``chunk``: chunk size the chunked/Pallas
    schedules would use — below two chunks the fix-up passes don't pay for
    themselves and the associative scan wins.  Returns one of
    "sequential" | "associative" | "chunked" | "pallas".
    """
    if t <= SEQUENTIAL_MAX_T:
        return "sequential"
    if backend is None:
        backend = jax.default_backend()
    if t >= PALLAS_MIN_T and backend == "tpu":
        return "pallas"
    if t >= 2 * chunk:
        return "chunked"
    return "associative"


def _pallas_scan_q(lam_q, x_q, n_real: int, h0, *, time_axis: int):
    """Q-basis scan through the Pallas kernel wrapper.

    Real eigen-slots ride along as zero-imaginary complex lanes so one kernel
    launch covers the whole state vector: a (N,) packed Q coefficient vector
    becomes (n_real + n_pairs,) complex, x/h likewise.
    """
    xt = jnp.moveaxis(x_q, time_axis, -2)          # (..., T, N)
    lead = xt.shape[:-2]
    t, n = xt.shape[-2], xt.shape[-1]
    nr = n_real

    def to_complex(v):
        """Packed Q layout -> one complex vector (reals ride with zero imag)."""
        vr, vc = scan_mod.q_split(v, nr)
        return jnp.concatenate(
            [jax.lax.complex(vr, jnp.zeros_like(vr)), vc], axis=-1)

    a_c = to_complex(lam_q)
    x_c = to_complex(xt.reshape((-1, t, n)))       # (B, T, nc)
    h_c = None
    if h0 is not None:
        h_c = to_complex(jnp.broadcast_to(h0, lead + (n,)).reshape((-1, n)))
    out = kernel_ops.diag_scan(a_c, x_c, h_c)      # (B, T, nc) complex
    hs = scan_mod.q_merge(out[..., :nr].real, out[..., nr:], x_q.dtype)
    return jnp.moveaxis(hs.reshape(lead + (t, n)), -2, time_axis)


def run_scan_q(lam_q, x_q, n_real: int, h0=None, *, method: str = "auto",
               chunk: int = 128, time_axis: int = -2,
               backend: Optional[str] = None):
    """Execute the Q-basis diagonal scan with an auto-selected backend.

    ``x_q``: (..., T, N) with time on ``time_axis``; ``lam_q``: (N,) packed
    (see ``core.scan.pack_lambda_q``); ``h0``: optional (..., N) initial state.
    ``method="auto"`` resolves via :func:`resolve_method`; explicit method
    strings pass straight through (so callers can still pin a backend).
    """
    if method == "auto":
        xt_shape = jnp.shape(x_q)
        t = xt_shape[time_axis % len(xt_shape)]
        method = resolve_method(t, backend=backend, chunk=chunk)
    if method == "pallas":
        return _pallas_scan_q(lam_q, x_q, n_real, h0, time_axis=time_axis)
    return scan_mod.diag_scan_q(lam_q, x_q, n_real, h0, method=method,
                                chunk=chunk, time_axis=time_axis)


# --------------------------------------------------------------------------- #
# Fused multi-token closed-loop decode                                         #
# --------------------------------------------------------------------------- #
def resolve_decode_method(backend: Optional[str] = None) -> str:
    """Backend for the fused K-token decode: the Pallas kernel on TPU, the
    jnp reference everywhere else.  Unlike prefill there is no T threshold —
    decode work is always step-serial, the only question is who runs it."""
    if backend is None:
        backend = jax.default_backend()
    return "pallas" if backend == "tpu" else "ref"


def _q_lanes(v, nr: int, axis: int = -1):
    """Packed Q layout -> (re, im) lane arrays along ``axis``: real slots
    first (zero imag), then the (re, im) interleaved pairs de-interleaved.
    Width nc = nr + (N - nr) // 2."""
    sl = [slice(None)] * v.ndim
    sl[axis] = slice(None, nr)
    reals = v[tuple(sl)]
    sl[axis] = slice(nr, None, 2)
    pre = v[tuple(sl)]
    sl[axis] = slice(nr + 1, None, 2)
    pim = v[tuple(sl)]
    re = jnp.concatenate([reals, pre], axis=axis)
    im = jnp.concatenate([jnp.zeros_like(reals), pim], axis=axis)
    return re, im


def _q_repack(re, im, nr: int):
    """Inverse of ``_q_lanes`` on the last axis: real lanes back in front,
    pair lanes re-interleaved to the packed layout."""
    pre, pim = re[..., nr:], im[..., nr:]
    pairs = jnp.stack([pre, pim], axis=-1).reshape(
        pre.shape[:-1] + (2 * pre.shape[-1],))
    return jnp.concatenate([re[..., :nr], pairs], axis=-1)


def run_decode_fused(lam_q, n_real: int, w_drive, w_out, states, y_prev,
                     mask, k: int, *, use_bias: bool, use_feedback: bool,
                     ensemble: str = "off", method: str = "auto",
                     backend: Optional[str] = None):
    """Execute K fused closed-loop decode steps over the slot block.

    ``lam_q``: (N,) packed — or (B, N) for a slot-batched param stack (the
    batched case is implied by ``lam_q.ndim == 2``); ``w_drive``: the
    pre-summed drive map ``win_q (+ wfb_q)`` (D, N) / (B, D, N) — closed loop
    feeds y back as u, so the two matmuls fuse into one; ``w_out``:
    (F, D) / (B, F, D) readout with rows ``[bias? | y_prev? | states]``
    (``core.esn.assemble_features`` order); ``states``/``y_prev``/``mask``:
    the (B, N)/(B, D)/(B,) arena arrays.  Returns ``(states', y_prev', ys)``
    in the packed layout, ``ys`` (k, B, D) — numerics identical to K
    ``arena.decode_step`` feedback steps (pinned by test).
    """
    if method == "auto":
        method = resolve_decode_method(backend)
    nr = n_real
    d = y_prev.shape[-1]
    a_re, a_im = _q_lanes(lam_q, nr)
    h_re, h_im = _q_lanes(states, nr)
    wd_re, wd_im = _q_lanes(w_drive, nr)

    idx = 0
    if use_bias:
        b_out = w_out[..., 0, :]
        idx = 1
    else:
        b_out = jnp.zeros(w_out.shape[:-2] + (d,), w_out.dtype)
    if use_feedback:
        wy = w_out[..., idx:idx + d, :]
        idx += d
    else:
        wy = jnp.zeros(w_out.shape[:-2] + (d, d), w_out.dtype)
    wh_re, wh_im = _q_lanes(w_out[..., idx:, :], nr, axis=-2)

    y0 = y_prev
    if ensemble == "mean":
        # Seed parity with arena.closed_loop: the first fed-back input of
        # every masked slot is the ensemble mean of the masked seeds.
        m = jnp.asarray(mask, y0.dtype)[:, None]
        denom = jnp.maximum(jnp.sum(m), 1.0)
        y_mean = jnp.sum(y0 * m, axis=0, keepdims=True) / denom
        y0 = jnp.where(m > 0.5, jnp.broadcast_to(y_mean, y0.shape), y0)

    fn = (kernel_ops.decode_fused if method == "pallas"
          else kernel_refs.decode_fused_ref)
    h_re, h_im, y, ys = fn(a_re, a_im, h_re, h_im, y0, wd_re, wd_im, wy,
                           b_out, wh_re, wh_im, mask, k=k, ensemble=ensemble)
    return _q_repack(h_re, h_im, nr), y, ys
