"""The Kuramoto-Sivashinsky field ``u_t = -u u_x - u_xx - u_xxxx`` on a
periodic domain of length ``L``, sampled on ``q`` grid points every ``dt``
time units (Pathak et al., Chaos 27, 121102 (2017): L=22, Q=64, dt=0.25).

Integrated in Fourier space by ETDRK4 with the coefficients evaluated by
contour integrals (Kassam & Trefethen, SIAM J. Sci. Comput. 26 (2005)), one
step of ``dt`` per sample.  ``rng`` draws a small zero-mean initial field; a
spin-up of ``SPIN_UP`` time units carries it onto the attractor and is
discarded.  The field is used as integrated (no normalisation): its
standard deviation on the attractor is about 1.3.
"""
from __future__ import annotations

import numpy as np

#: Time units integrated and discarded before the first sample.
SPIN_UP = 1000.0
#: Points on the contour of the coefficient integrals.
CONTOUR = 16


def _coefficients(L: float, q: int, dt: float):
    """ETDRK4 factors for the linear operator ``k^2 - k^4`` on ``q // 2 + 1``
    real-FFT modes, and the nonlinear factor ``-i k / 2``."""
    k = 2.0 * np.pi / L * np.arange(q // 2 + 1)
    lin = k ** 2 - k ** 4
    e, e2 = np.exp(dt * lin), np.exp(dt * lin / 2.0)
    r = np.exp(1j * np.pi * (np.arange(1, CONTOUR + 1) - 0.5) / CONTOUR)
    lr = dt * lin[:, None] + r[None, :]
    ex = np.exp(lr)
    qf = dt * np.real(np.mean((np.exp(lr / 2.0) - 1.0) / lr, axis=1))
    f1 = dt * np.real(np.mean((-4.0 - lr + ex * (4.0 - 3.0 * lr + lr ** 2))
                              / lr ** 3, axis=1))
    f2 = dt * np.real(np.mean((2.0 + lr + ex * (lr - 2.0)) / lr ** 3, axis=1))
    f3 = dt * np.real(np.mean((-4.0 - 3.0 * lr - lr ** 2 + ex * (4.0 - lr))
                              / lr ** 3, axis=1))
    g = -0.5j * k
    g[-1] = 0.0          # the Nyquist mode's derivative is zero
    return e, e2, qf, f1, f2, f3, g


def generate(rng, length: int, *, L: float, q: int, dt: float) -> np.ndarray:
    """``(length, q)`` float64 samples of the field on the attractor."""
    e, e2, qf, f1, f2, f3, g = _coefficients(L, q, dt)

    def nonlinear(v):
        return g * np.fft.rfft(np.fft.irfft(v, n=q) ** 2)

    u0 = 0.1 * rng.standard_normal(q)
    v = np.fft.rfft(u0 - u0.mean())
    out = np.empty((length, q))
    spin = int(round(SPIN_UP / dt))
    for t in range(spin + length):
        nv = nonlinear(v)
        a = e2 * v + qf * nv
        na = nonlinear(a)
        b = e2 * v + qf * na
        nb = nonlinear(b)
        c = e2 * a + qf * (2.0 * nb - nv)
        nc = nonlinear(c)
        v = e * v + nv * f1 + 2.0 * (na + nb) * f2 + nc * f3
        if t >= spin:
            out[t - spin] = np.fft.irfft(v, n=q)
    return out
