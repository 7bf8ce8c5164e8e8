"""The configuration's model, made by the benchmark from the seed.

A diagonal linear reservoir (arXiv 2602.19802): state h_t (NC complex lanes:
``n_real`` real eigenvalues, then one lane per conjugate pair), recurrence
``h_t = lam * h_{t-1} + u_t @ w_in`` and readout
``y_t = w_out[0] + packed(h_t) @ w_out[1:]``, where ``packed`` lays a state
out as ``[reals | re_0, im_0, re_1, im_1, ...]`` (N real numbers).

The eigenvalues follow Direct Parameter Generation's noisy golden spiral
(the paper's Algorithm 3), the leak is folded in, and the input map is drawn
in the eigenbasis.  The readout is a ridge fit on the host in float64.
Everything here is numpy: the plain reference (``bench.reference``) and the
program under test both take these arrays, and neither makes its own.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from . import signals


@dataclasses.dataclass(frozen=True)
class Model:
    lam: np.ndarray        # (NC,) complex128
    w_in: np.ndarray       # (D_in, NC) complex128
    w_out: np.ndarray      # (1 + N, D_out) float64: bias, then packed state
    n_real: int

    @property
    def n(self) -> int:
        return self.n_real + 2 * (self.lam.shape[0] - self.n_real)


def n_real_for(n: int) -> int:
    """Real eigenvalues of an N x N random real matrix: about sqrt(2N/pi),
    rounded so that the complex lanes pair up."""
    nr = int(round(math.sqrt(2.0 * n / math.pi)))
    return nr + ((n - nr) % 2)


def golden_spectrum(n: int, rho: float, sigma: float, rng):
    """Noisy golden spiral: pairs on a golden-angle phyllotaxis over the
    upper half disk, reals uniform, all scaled to radius ``rho``, then
    complex Gaussian noise ``sigma`` on the pairs."""
    nr = n_real_for(n)
    npair = (n - nr) // 2
    lam_r = rng.uniform(-1.0, 1.0, nr)
    v0 = rng.uniform(0.0, 2.0)
    k = np.arange(1, 4 * npair + 65, dtype=np.float64)
    v = (v0 + k * (3.0 - math.sqrt(5.0))) % 2.0
    acc = v < 1.0
    k, v = k[acc][:npair], v[acc][:npair]
    lam_c = np.sqrt(k / (2.0 * npair)) * np.exp(1j * math.pi * v)
    scale = rho / max(np.abs(lam_r).max(), np.abs(lam_c).max())
    lam_r, lam_c = lam_r * scale, lam_c * scale
    lam_c = lam_c + rng.normal(0, sigma, npair) + 1j * rng.normal(0, sigma,
                                                                   npair)
    lam_c = np.where(lam_c.imag < 0, np.conj(lam_c), lam_c)
    return lam_r, lam_c


def packed(h: np.ndarray, n_real: int) -> np.ndarray:
    """Complex lanes (..., NC) -> packed real layout (..., N)."""
    pairs = h[..., n_real:]
    out = np.empty(h.shape[:-1] + (n_real + 2 * pairs.shape[-1],))
    out[..., :n_real] = h[..., :n_real].real
    out[..., n_real::2] = pairs.real
    out[..., n_real + 1::2] = pairs.imag
    return out


def fit_readout(lam, w_in, n_real, u, y, *, washout: int, alpha: float,
                block: int = 2048) -> np.ndarray:
    """Ridge readout on [1, packed(h_t)] for t >= washout, float64; the Gram
    matrix accumulates in blocks of time steps."""
    n_feat = 1 + n_real + 2 * (lam.shape[0] - n_real)
    gram = np.zeros((n_feat, n_feat))
    cross = np.zeros((n_feat, y.shape[1]))
    drive = u @ w_in
    h = np.zeros(lam.shape, complex)
    rows = []
    for t in range(u.shape[0]):
        h = lam * h + drive[t]
        if t >= washout:
            rows.append(h)
        if len(rows) == block or (t == u.shape[0] - 1 and rows):
            x = np.concatenate([np.ones((len(rows), 1)),
                                packed(np.stack(rows), n_real)], axis=1)
            tgt = y[t + 1 - len(rows):t + 1]
            gram += x.T @ x
            cross += x.T @ tgt
            rows = []
    gram[np.diag_indices_from(gram)] += alpha
    return np.linalg.solve(gram, cross)


def build(config: dict, seed: int):
    """(Model, signal) for ``config`` from ``seed``: the same seed gives the
    same arrays.  ``signal`` (T, D) is the stream prompts are cut from; its
    first ``fit.train_steps + 1`` samples train the readout."""
    m, fit = config["model"], config["fit"]
    rng = np.random.default_rng(seed)
    lam_r, lam_c = golden_spectrum(m["n"], m["spectral_radius"],
                                   m["dpg_sigma"], rng)
    leak = m["leak"]
    lam = np.concatenate([lam_r, lam_c]).astype(complex) * leak + (1 - leak)
    nr = lam_r.shape[0]
    s = m["input_scaling"] * leak
    w_in = rng.uniform(-s, s, (m["d_in"], lam.shape[0])) + 0j
    w_in[:, nr:] += 1j * rng.uniform(-s, s, (m["d_in"], lam.shape[0] - nr))
    sig = signals.generate(config["signal"], rng, config["signal_steps"])
    t = fit["train_steps"]
    w_out = fit_readout(lam, w_in, nr, sig[:t], sig[1:t + 1],
                        washout=fit["washout"], alpha=fit["ridge_alpha"])
    return Model(lam=lam, w_in=w_in, w_out=w_out, n_real=nr), sig


def closed_loop_growth(model: Model, steps: int, rng, probes: int = 4
                       ) -> float:
    """Largest growth ``|A^steps x| / |x|`` of the free-running loop
    ``h -> lam h + (packed(h) @ W_h) @ w_in`` over random probes (the bias
    only shifts the fixed point).  Above 1 the loop amplifies over the
    horizon."""
    wh = model.w_out[1:]
    h = rng.standard_normal((probes, model.lam.shape[0])) + 0j
    h[:, model.n_real:] += 1j * rng.standard_normal(
        (probes, model.lam.shape[0] - model.n_real))
    norm0 = np.linalg.norm(packed(h, model.n_real), axis=1)
    for _ in range(steps):
        h = model.lam * h + (packed(h, model.n_real) @ wh) @ model.w_in
    return float((np.linalg.norm(packed(h, model.n_real), axis=1)
                  / norm0).max())


def to_program(model: Model, config: dict, dtype):
    """The program's own parameter structs, built from these arrays."""
    import jax.numpy as jnp
    from repro.core.params import DiagParams, ESNConfig, Readout
    m = config["model"]
    nr = model.n_real
    lam_q = packed(model.lam, nr)
    win_q = packed(model.w_in, nr)
    cfg = ESNConfig(n=model.n, d_in=m["d_in"], d_out=m["d_out"],
                    spectral_radius=m["spectral_radius"], leak=m["leak"],
                    input_scaling=m["input_scaling"],
                    ridge_alpha=config["fit"]["ridge_alpha"], use_bias=True)
    # qtq is the readout-fit metric Q^T Q; the eigenbasis here is taken as
    # orthonormal, and serving never reads it.
    params = DiagParams(lam_q=jnp.asarray(lam_q, dtype),
                        win_q=jnp.asarray(win_q, dtype), wfb_q=None,
                        qtq=jnp.eye(model.n, dtype=dtype), cfg=cfg,
                        n_real=nr)
    return params, Readout(jnp.asarray(model.w_out, dtype))
