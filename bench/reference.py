"""The plain reference, and the control that must fail against it.

``reference_outputs`` is the float64 recurrence and readout in numpy, written
here and independent of the program (after the host reference of the
repository's bring-up smoke): every compared request runs its prompt from a
zero state, then free-runs its horizon, feeding each output back as the next
input.  ``control_outputs`` computes the same in float32 with every dot in
three bfloat16 passes, JAX's ``high`` precision, the step below the float32 at
full precision that the configurations state; it stands in the program's
place to show that the comparison catches that step.
"""
from __future__ import annotations

import numpy as np

from .model import Model, packed


def _left_pad(prompts, d):
    """Prompts of different lengths, right-aligned on one time axis.  A zero
    state under zero input stays zero, so leading zeros are exact."""
    t_max = max(p.shape[0] for p in prompts)
    out = np.zeros((len(prompts), t_max, d))
    for i, p in enumerate(prompts):
        out[i, t_max - p.shape[0]:] = p
    return out


def reference_outputs(model: Model, prompts, horizons, block: int = 256):
    """Served tokens as the float64 reference has them: a list of
    (horizon_r, D) arrays.  The drive is formed ``block`` steps at a time."""
    nr, b, wh = model.n_real, model.w_out[0], model.w_out[1:]
    u = _left_pad(prompts, model.w_in.shape[0])
    h = np.zeros((u.shape[0], model.lam.shape[0]), complex)
    for t0 in range(0, u.shape[1], block):
        drive = u[:, t0:t0 + block] @ model.w_in      # (R, block, NC)
        for t in range(drive.shape[1]):
            h = model.lam * h + drive[:, t]
    y = b + packed(h, nr) @ wh
    ys = []
    for _ in range(max(horizons)):
        h = model.lam * h + y @ model.w_in
        y = b + packed(h, nr) @ wh
        ys.append(y)
    ys = np.stack(ys, axis=1)
    return [ys[i, :n] for i, n in enumerate(horizons)]


def _dot_high(x, w):
    """f32 product in three bfloat16 passes (JAX ``Precision.HIGH``), written
    out so that it computes the same on every backend.  Each operand splits
    into a bfloat16 head and tail by ``reduce_precision``, which the compiler
    may not fold away as it may a cast down and back up; the products of
    those bfloat16 values are exact in float32 at full precision."""
    import jax
    import jax.numpy as jnp

    def split(v):
        hi = jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)
        return hi, jax.lax.reduce_precision(v - hi, exponent_bits=8,
                                            mantissa_bits=7)

    xh, xl = split(x)
    wh, wl = split(w)

    def dot(a, c):
        return jnp.matmul(a, c, precision=jax.lax.Precision.HIGHEST)
    return dot(xh, wh) + dot(xh, wl) + dot(xl, wh)


def control_outputs(model: Model, prompts, horizons):
    """The reference at ``high`` precision in float32 (see module doc)."""
    import jax
    import jax.numpy as jnp
    f32 = jnp.float32
    nr = model.n_real
    lam_re = jnp.asarray(model.lam.real, f32)
    lam_im = jnp.asarray(model.lam.imag, f32)
    win = jnp.asarray(np.concatenate([model.w_in.real, model.w_in.imag], 1),
                      f32)
    b = jnp.asarray(model.w_out[0], f32)
    # Readout rows reordered to [re lanes | im lanes] (real lanes' im rows 0).
    nc = model.lam.shape[0]
    wh = np.zeros((2 * nc, model.w_out.shape[1]))
    wh[:nr] = model.w_out[1:1 + nr]
    wh[nr:nc] = model.w_out[1 + nr::2]
    wh[nc + nr:] = model.w_out[2 + nr::2]
    wh = jnp.asarray(wh, f32)
    u = jnp.asarray(_left_pad(prompts, model.w_in.shape[0]), f32)

    def step(h, d):
        hr, hi = h
        return (lam_re * hr - lam_im * hi + d[..., :nc],
                lam_re * hi + lam_im * hr + d[..., nc:])

    def readout(h):
        return b + _dot_high(jnp.concatenate(h, -1), wh)

    @jax.jit
    def run(u):
        drive = _dot_high(u, win)                  # (R, T, 2NC)
        zero = jnp.zeros((u.shape[0], nc), f32)
        h, _ = jax.lax.scan(lambda h, d: (step(h, d), None), (zero, zero),
                            jnp.moveaxis(drive, 1, 0))

        def free(carry, _):
            h, y = carry
            h = step(h, _dot_high(y, win))
            y = readout(h)
            return (h, y), y
        _, ys = jax.lax.scan(free, (h, readout(h)), None,
                             length=max(horizons))
        return jnp.moveaxis(ys, 0, 1)

    ys = np.asarray(run(u), np.float64)
    return [ys[i, :n] for i, n in enumerate(horizons)]


def gap(served, want) -> float:
    """Widest distance of a served token from the reference's, as a share
    of the root mean square of the reference's tokens over all compared
    requests.  A request that returned the wrong number of tokens, or a
    non-finite one, reads infinite."""
    scale = float(np.sqrt(np.mean(np.concatenate(
        [w.ravel() for w in want]) ** 2)))
    worst = 0.0
    for got, w in zip(served, want):
        got = np.asarray(got, np.float64)
        if got.shape != w.shape or not np.isfinite(got).all():
            return float("inf")
        worst = max(worst, float(np.abs(got - w).max()))
    return worst / scale
