"""Linear Echo State Networks — standard and diagonalized (the paper's §2/§4).

The model is a *pytree of parameters* plus *pure functions over it*:

* Builders return immutable param structs (``core.params``):
  ``standard_params(cfg)`` -> :class:`StandardParams` (dense W, O(N^2) step);
  ``diag_params(cfg)`` -> :class:`DiagParams` (eigendecomposed, O(N) step);
  ``dpg_params(cfg, distribution)`` -> :class:`DiagParams` sampled directly
  (uniform / golden / noisy_golden / sim) — no W is ever built.
* ``run(params, u)`` collects states; ``fit(params, u, y)`` ridge-trains and
  returns a :class:`Readout`; ``predict(params, readout, u)`` and
  ``generate(params, readout, n_steps, ...)`` evaluate it.  All of these are
  pure — ``jax.jit``/``jax.vmap``/``shard_map`` them freely, including over a
  *batch* of param structs (:func:`core.params.stack_params`).

The diagonal model runs entirely in the real Q basis (Appendix A memory-view
trick): states are real vectors ``[real slots | (re, im) pairs]``, the
recurrence is ``scan.diag_scan_q`` (backend picked by ``core.dispatch``) and
readout training uses the generalized ridge with metric ``blockdiag(I, Q^T Q)``
(Eq. 29) — numerically identical to standard ridge + EWT.  Readout trained
directly in the eigenbasis = **EET**; transplanted from a trained standard
model via ``ewt_readout`` = **EWT**.

:class:`LinearESN` remains as a thin stateful *facade* over (params, readout,
basis) for interactive use; its mutating methods (``.fit`` storing ``.w_out``)
are a deprecation shim kept for one release — new code should hold the structs
and call the pure functions.

Row-vector convention throughout (as the paper): r (T, N), W_in (D_in, N),
W (N, N) acting on the right, W_out (N', D_out).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import dispatch as dispatch_mod
from . import ridge as ridge_mod
from . import scan as scan_mod
from .basis import EigenBasis
from .params import DiagParams, ESNConfig, Readout, StandardParams
from .spectral import dpg as dpg_gen, generate_reservoir_matrix

__all__ = [
    "ESNConfig",
    "LinearESN",
    "standard_params",
    "diag_params",
    "dpg_params",
    "ewt_readout",
    "drive",
    "step_states",
    "scan_states",
    "run",
    "assemble_features",
    "features",
    "eet_metric",
    "fit",
    "fit_host",
    "predict",
    "generate",
]


# --------------------------------------------------------------------- build
def _gen_input_matrix(rng, d, n, scale, connectivity):
    w = rng.uniform(-1.0, 1.0, size=(d, n)) * scale
    if connectivity < 1.0:
        w *= rng.uniform(0.0, 1.0, size=(d, n)) < connectivity
    return w


def _gen_weights(cfg: ESNConfig):
    """Host-side raw (W, W_in, W_fb) generation shared by every builder."""
    rng = np.random.default_rng(cfg.seed)
    w = generate_reservoir_matrix(cfg.n, cfg.spectral_radius, rng,
                                  cfg.connectivity)
    w_in = _gen_input_matrix(rng, cfg.d_in, cfg.n, cfg.input_scaling,
                             cfg.input_connectivity)
    w_fb = (_gen_input_matrix(rng, cfg.d_out, cfg.n, cfg.feedback_scaling, 1.0)
            if cfg.use_feedback else None)
    return w, w_in, w_fb


def _standard_struct(cfg: ESNConfig, w, w_in, w_fb) -> StandardParams:
    """The one leak-fold (Eq. 4) -> StandardParams construction."""
    lr = cfg.leak
    return StandardParams(
        w=jnp.asarray(lr * w + (1.0 - lr) * np.eye(cfg.n)),
        w_in=jnp.asarray(lr * w_in),
        w_fb=None if w_fb is None else jnp.asarray(lr * w_fb),
        cfg=cfg)


def standard_params(cfg: ESNConfig) -> StandardParams:
    """Dense-W params (the paper's baseline), leak folded in (Eq. 4)."""
    return _standard_struct(cfg, *_gen_weights(cfg))


def _diag_from_basis(cfg: ESNConfig, basis: EigenBasis, w_in_raw,
                     w_fb_raw) -> DiagParams:
    lr = cfg.leak
    # Leak acts in the eigendomain: eig(lr W + (1-lr) I) = lr L + (1-lr),
    # same eigenvectors — no re-decomposition needed.
    lam_real = lr * basis.spectrum.lam_real + (1.0 - lr)
    lam_cpx = lr * basis.spectrum.lam_cpx + (1.0 - lr)
    return DiagParams(
        lam_q=scan_mod.pack_lambda_q(jnp.asarray(lam_real),
                                     jnp.asarray(lam_cpx)),
        win_q=jnp.asarray(basis.win_to_q(lr * w_in_raw)),
        wfb_q=(jnp.asarray(basis.win_to_q(lr * w_fb_raw))
               if w_fb_raw is not None else None),
        qtq=jnp.asarray(basis.qtq()),
        cfg=cfg, n_real=basis.n_real)


def _diag_parts(cfg: ESNConfig):
    """Host-side (basis, w_raw, w_in_raw, w_fb_raw) for the eigendecomposed
    path — one copy shared by the pure builder and the facade."""
    w, w_in, w_fb = _gen_weights(cfg)
    return EigenBasis.from_matrix(w), w, w_in, w_fb


def diag_params(cfg: ESNConfig) -> DiagParams:
    """Generate a standard W, then diagonalize (EWT/EET path, paper §4.2-4.3)."""
    basis, _, w_in, w_fb = _diag_parts(cfg)
    return _diag_from_basis(cfg, basis, w_in, w_fb)


def _dpg_parts(cfg: ESNConfig, distribution: str, sigma: float):
    """Host-side (basis, w_in_raw, w_fb_raw) for the DPG path — one copy
    shared by the pure builder and the facade (incl. the seed+1 offset)."""
    spec, p = dpg_gen(cfg.n, cfg.spectral_radius, cfg.seed, distribution,
                      sigma=sigma, connectivity=cfg.connectivity)
    rng = np.random.default_rng(cfg.seed + 1)
    w_in = _gen_input_matrix(rng, cfg.d_in, cfg.n, cfg.input_scaling,
                             cfg.input_connectivity)
    w_fb = (_gen_input_matrix(rng, cfg.d_out, cfg.n, cfg.feedback_scaling, 1.0)
            if cfg.use_feedback else None)
    return EigenBasis.from_spectral(spec, p), w_in, w_fb


def dpg_params(cfg: ESNConfig, distribution: str = "noisy_golden",
               sigma: float = 0.2) -> DiagParams:
    """Direct Parameter Generation (paper §4.4) — no W is ever built."""
    basis, w_in, w_fb = _dpg_parts(cfg, distribution, sigma)
    return _diag_from_basis(cfg, basis, w_in, w_fb)


def ewt_readout(basis: EigenBasis, cfg: ESNConfig,
                trained: Readout) -> Readout:
    """EWT (paper §4.2): transplant a standard-trained readout into the Q
    basis (the models must share the same underlying W / W_in)."""
    w_out = np.asarray(trained.w_out)
    n_extra = w_out.shape[0] - cfg.n
    top = w_out[:n_extra]
    res = basis.wout_res_to_q(w_out[n_extra:])  # Q^-1 W_out,res (real)
    return Readout(jnp.asarray(np.concatenate([top, res], axis=0)))


# ----------------------------------------------------------------------- run
def drive(params, u, y_prev=None):
    """Input drive into the recurrence: ``u @ W_in (+ y_prev @ W_fb)``, in the
    model's native basis.  The single copy of this expression — the serving
    engine and the scans below all route through it."""
    if params.mode == "diag":
        d = u @ params.win_q
        if params.cfg.use_feedback:
            d = d + y_prev @ params.wfb_q
    else:
        d = u @ params.w_in
        if params.cfg.use_feedback:
            d = d + y_prev @ params.w_fb
    return d


def step_states(params, states, d):
    """One recurrence application in the native basis: O(N) element-wise
    (diag) or dense O(N^2) (standard)."""
    if params.mode == "diag":
        return scan_mod.realified_multiply(states, params.lam_q,
                                           params.n_real) + d
    return states @ params.w + d


def scan_states(params, d, h0=None, *, method: str = "auto",
                chunk: int = 128):
    """Run the recurrence over a precomputed drive (..., T, N) from state
    ``h0`` (native basis; zeros when None).  Time is axis -2 in both modes;
    leading axes are batch.  The one scan entry point for both modes —
    ``run`` and the serving engine's prefill share it."""
    if params.mode == "diag":
        return dispatch_mod.run_scan_q(params.lam_q, d, params.n_real, h0,
                                       method=method, chunk=chunk,
                                       time_axis=-2)
    if h0 is None:
        h0 = jnp.zeros(d.shape[:-2] + (params.cfg.n,), d.dtype)

    def step(r, di):
        r = step_states(params, r, di)
        return r, r

    _, states = jax.lax.scan(step, h0, jnp.moveaxis(d, -2, 0))
    return jnp.moveaxis(states, 0, -2)


def _shift_teacher(cfg: ESNConfig, y_teacher, dtype):
    """Teacher outputs aligned as feedback: y_prev(t) = y(t-1), y_prev(0)=0."""
    return jnp.concatenate(
        [jnp.zeros((1, cfg.d_out), dtype), y_teacher[:-1]], axis=0)


def run(params, u, y_teacher=None, *, method: str = "auto", chunk: int = 128):
    """Collect reservoir states for input u (T, D_in).  Returns (T, N) — raw
    states (standard mode) or Q-basis states (diag mode).

    ``method="auto"`` (default) lets ``core.dispatch`` pick the scan backend
    from the prompt shape (sequential / associative / chunked / Pallas);
    explicit strings pin one."""
    u = jnp.asarray(u)
    cfg = params.cfg
    y_prev = None
    if cfg.use_feedback:
        if y_teacher is None:
            raise ValueError("feedback ESN needs teacher outputs to collect "
                             "states (closed-loop: use generate)")
        y_prev = _shift_teacher(cfg, jnp.asarray(y_teacher), u.dtype)
    return scan_states(params, drive(params, u, y_prev), method=method,
                       chunk=chunk)


def assemble_features(params, states, y_prev=None):
    """X = [1 | y_prev | r] from an already-aligned feedback column (no
    shifting) — shared by training-time ``features`` and the engine's
    streaming paths."""
    cfg = params.cfg
    cols = []
    if cfg.use_bias:
        cols.append(jnp.ones(states.shape[:-1] + (1,), states.dtype))
    if cfg.use_feedback:
        cols.append(y_prev)
    cols.append(states)
    return jnp.concatenate(cols, axis=-1)


def features(params, states, y_teacher=None):
    """X(t) = [1 | y(t-1) | r(t)] (paper Eq. 7) from collected states."""
    y_prev = None
    if params.cfg.use_feedback:
        y_prev = _shift_teacher(params.cfg, jnp.asarray(y_teacher),
                                states.dtype)
    return assemble_features(params, states, y_prev)


def eet_metric(params: DiagParams):
    """EET regularizer metric blockdiag(I, Q^T Q) (Eq. 29)."""
    cfg = params.cfg
    n_extra = cfg.n_features - cfg.n
    m = jnp.zeros((cfg.n_features, cfg.n_features), params.qtq.dtype)
    m = m.at[jnp.arange(n_extra), jnp.arange(n_extra)].set(1.0)
    return m.at[n_extra:, n_extra:].set(params.qtq)


# ----------------------------------------------------------------------- fit
def fit(params, u, y, washout: int = 0, alpha: Optional[float] = None,
        method: str = "auto") -> Readout:
    """Ridge-train a readout; returns a fresh immutable :class:`Readout`.
    Standard mode: Eq. 9.  Diag mode: EET (Eq. 29, generalized metric) —
    numerically equal to standard+EWT."""
    u = jnp.asarray(u)
    y = jnp.asarray(y)
    alpha = params.cfg.ridge_alpha if alpha is None else alpha
    states = run(params, u,
                 y_teacher=y if params.cfg.use_feedback else None,
                 method=method)
    x = features(params, states, y_teacher=y)[washout:]
    yt = y[washout:]
    g, c = ridge_mod.gram(x, yt)
    if params.mode == "standard":
        return Readout(ridge_mod.ridge_solve(g, c, alpha))
    return Readout(ridge_mod.ridge_solve_general(g, c, eet_metric(params),
                                                 alpha))


def fit_host(params: DiagParams, u, y, washout: int = 0,
             alpha: Optional[float] = None) -> Readout:
    """:func:`fit` for a diag-mode model, run on the host in numpy float64.

    The offline readout fit of the serving deployments.  The device path
    runs in float32, and ridge alphas down to 1e-8 are not well posed in
    float32 normal equations; so the states are recomputed here in float64
    from the params as stored (float32 on the chip), the EET-regularized
    system (Eq. 29) is solved in float64, and the readout is returned in the
    params' dtype — what serving applies."""
    if params.mode != "diag":
        raise ValueError("fit_host takes diag-mode params")
    cfg = params.cfg
    alpha = cfg.ridge_alpha if alpha is None else alpha
    nr = params.n_real
    u = np.asarray(u, np.float64)
    y = np.asarray(y, np.float64)
    d = u @ np.asarray(params.win_q, np.float64)
    y_prev = None
    if cfg.use_feedback:
        y_prev = np.concatenate([np.zeros((1, cfg.d_out)), y[:-1]], axis=0)
        d = d + y_prev @ np.asarray(params.wfb_q, np.float64)

    def to_complex(v):        # packed Q layout -> complex lanes
        return np.concatenate([v[..., :nr], v[..., nr::2] + 1j * v[..., nr + 1::2]],
                              axis=-1)

    lam = to_complex(np.asarray(params.lam_q, np.float64))
    d_c = to_complex(d)
    h = np.zeros_like(lam)
    hs = np.empty_like(d_c)
    for t in range(d_c.shape[0]):
        h = lam * h + d_c[t]
        hs[t] = h
    states = np.empty_like(d)
    states[:, :nr] = hs[:, :nr].real
    states[:, nr::2] = hs[:, nr:].real
    states[:, nr + 1::2] = hs[:, nr:].imag
    cols = [np.ones((len(d), 1))] if cfg.use_bias else []
    if cfg.use_feedback:
        cols.append(y_prev)
    x = np.concatenate(cols + [states], axis=1)[washout:]
    n_extra = x.shape[1] - cfg.n
    metric = np.eye(x.shape[1])
    metric[n_extra:, n_extra:] = np.asarray(params.qtq, np.float64)
    w = np.linalg.solve(x.T @ x + alpha * metric, x.T @ y[washout:])
    return Readout(jnp.asarray(w, params.dtype))


def predict(params, readout: Readout, u, y_teacher=None,
            method: str = "auto"):
    """Readout predictions over a teacher-forced run: X @ W_out."""
    states = run(params, u, y_teacher=y_teacher, method=method)
    x = features(params, states, y_teacher=y_teacher)
    return x @ readout.w_out


# ------------------------------------------------------------------ generate
def generate(params, readout: Readout, n_steps: int, u_warm, y_warm):
    """Closed-loop generation: feed predicted y back as next input
    (output-as-input autonomy, D_in == D_out).

    Teacher-forced warmup (time-parallel scan), then a free-running
    ``lax.scan``.  After the warmup the loop is seeded with the teacher's
    last output for feedback models, and with the last warmup prediction
    otherwise.  Pure in (params, readout) — jit with ``n_steps`` static.
    """
    cfg = params.cfg
    if cfg.d_in != cfg.d_out:
        raise ValueError("closed loop requires d_in == d_out")
    u_warm = jnp.asarray(u_warm)
    y_warm = jnp.asarray(y_warm)
    states = run(params, u_warm,
                 y_teacher=y_warm if cfg.use_feedback else None)
    h = states[-1]
    if cfg.use_feedback:
        y0 = y_warm[-1].astype(h.dtype)
    else:
        x_last = assemble_features(params, states[-1:], None)
        y0 = (x_last @ readout.w_out)[0]
    use_fb = cfg.use_feedback
    w_out = readout.w_out

    def step(carry, _):
        hc, yc = carry
        hc = step_states(params, hc,
                         drive(params, yc, yc if use_fb else None))
        x = assemble_features(params, hc[None],
                              yc[None] if use_fb else None)[0]
        yn = x @ w_out
        return (hc, yn), yn

    (_, _), ys = jax.lax.scan(step, (h, y0), None, length=n_steps)
    return ys


# One shared compiled entry point: (params, readout) are traced pytree
# arguments, so a trace is valid for ANY readout of the same shapes — refits
# and in-place w_out swaps can never serve stale weights (the old engine-era
# cache baked w_out into its traces and keyed invalidation on array
# identity, which in-place swaps could miss), and a fit()/generate() sweep
# reuses one compilation instead of retracing per readout.
_generate_jit = jax.jit(generate, static_argnums=(2,))


# ------------------------------------------------------------------- facade
class LinearESN:
    """Thin facade over ``(params, readout, basis)`` for interactive use.

    Builders (``standard`` / ``diagonalized`` / ``dpg``) freeze the model
    into an immutable param struct at construction; the instance itself only
    carries that struct, the trained :class:`Readout`, and host-side basis /
    raw-matrix metadata for analysis (EWT transplants, Theorem 5).

    .. deprecated:: the mutating method API (``.fit`` storing ``.w_out`` on
       the instance) is a compatibility shim for one release — new code
       should call the module-level pure functions on ``.params`` directly
       (see the migration table in README).
    """

    def __init__(self, cfg: ESNConfig, mode: str, params=None, readout=None,
                 basis: Optional[EigenBasis] = None, w_raw=None,
                 w_in_raw=None, w_fb_raw=None):
        self.cfg = cfg
        self.mode = mode
        self.params = params
        self.readout: Optional[Readout] = readout
        self.basis = basis
        self.w_raw = w_raw
        self.w_in_raw = w_in_raw
        self.w_fb_raw = w_fb_raw

    # ------------------------------------------------------------ builders
    @staticmethod
    def standard(cfg: ESNConfig) -> "LinearESN":
        w, w_in, w_fb = _gen_weights(cfg)
        return LinearESN(cfg, "standard",
                         params=_standard_struct(cfg, w, w_in, w_fb),
                         w_raw=w, w_in_raw=w_in, w_fb_raw=w_fb)

    @staticmethod
    def diagonalized(cfg: ESNConfig) -> "LinearESN":
        basis, w, w_in, w_fb = _diag_parts(cfg)
        return LinearESN(cfg, "diag",
                         params=_diag_from_basis(cfg, basis, w_in, w_fb),
                         basis=basis, w_raw=w, w_in_raw=w_in, w_fb_raw=w_fb)

    @staticmethod
    def dpg(cfg: ESNConfig, distribution: str = "noisy_golden",
            sigma: float = 0.2) -> "LinearESN":
        basis, w_in, w_fb = _dpg_parts(cfg, distribution, sigma)
        return LinearESN(cfg, "diag",
                         params=_diag_from_basis(cfg, basis, w_in, w_fb),
                         basis=basis, w_in_raw=w_in, w_fb_raw=w_fb)

    # ------------------------------------------- param-struct passthroughs
    @property
    def w(self):
        return self.params.w

    @property
    def w_in(self):
        return self.params.w_in

    @property
    def w_fb(self):
        return self.params.w_fb

    @property
    def lam_q(self):
        return self.params.lam_q

    @property
    def win_q(self):
        return self.params.win_q

    @property
    def wfb_q(self):
        return self.params.wfb_q

    @property
    def qtq(self):
        return self.params.qtq

    @property
    def n_real(self):
        return self.params.n_real

    @property
    def w_out(self):
        return None if self.readout is None else self.readout.w_out

    @w_out.setter
    def w_out(self, value):
        # Deprecation shim: assigning w_out wraps it in a fresh immutable
        # Readout, so identity-keyed caches (generate) can never go stale.
        self.readout = None if value is None else Readout(jnp.asarray(value))

    # --------------------------------------------------------------- shims
    def ewt_from(self, trained_standard: "LinearESN") -> "LinearESN":
        """EWT (paper §4.2): transplant a trained standard readout into this
        diagonal model (must share the same underlying W/W_in)."""
        assert self.mode == "diag" and trained_standard.readout is not None
        self.readout = ewt_readout(self.basis, self.cfg,
                                   trained_standard.readout)
        return self

    def drive(self, u, y_prev=None):
        return drive(self.params, u, y_prev)

    def step_states(self, states, d):
        return step_states(self.params, states, d)

    def scan_states(self, d, h0=None, *, method: str = "auto",
                    chunk: int = 128):
        return scan_states(self.params, d, h0, method=method, chunk=chunk)

    def run(self, u, y_teacher=None, *, method: str = "auto",
            chunk: int = 128):
        return run(self.params, u, y_teacher, method=method, chunk=chunk)

    def assemble_features(self, states, y_prev=None):
        return assemble_features(self.params, states, y_prev)

    def features(self, states, y_teacher=None):
        return features(self.params, states, y_teacher)

    def _metric(self):
        return eet_metric(self.params)

    def fit(self, u, y, washout: int = 0, alpha: Optional[float] = None,
            method: str = "auto"):
        self.readout = fit(self.params, u, y, washout=washout, alpha=alpha,
                           method=method)
        return self

    def predict(self, u, y_teacher=None, method: str = "auto"):
        assert self.readout is not None, "fit() first"
        return predict(self.params, self.readout, u, y_teacher=y_teacher,
                       method=method)

    def generate(self, n_steps: int, u_warm, y_warm):
        """Closed-loop generation through the shared jitted pure
        :func:`generate`.  The current immutable :class:`Readout` is passed
        as a traced argument on every call, so refits and in-place ``w_out``
        swaps take effect immediately — the engine-era stale-cache bug
        (``eng.w_out is not self.w_out`` missing swaps) is impossible by
        construction, and the compiled trace is reused across refits."""
        assert self.readout is not None
        return _generate_jit(self.params, self.readout, int(n_steps),
                             jnp.asarray(u_warm), jnp.asarray(y_warm))

    # ----------------------------------------------- Theorem 5 (W_in-free R)
    def collect_r_states(self, u, *, method: str = "sequential"):
        """R(t) per §3.3 (diag mode): states independent of W_in.
        Returns (T, D_in, N) in Q layout."""
        assert self.mode == "diag"
        u = jnp.asarray(u)
        nr = self.n_real
        n = self.cfg.n
        # Input term in Q layout: u_d added to every real slot and to the Re
        # lane of every pair slot (adding a real scalar to a complex
        # coordinate).
        mask = np.zeros((n,))
        mask[:nr] = 1.0
        mask[nr::2] = 1.0
        x = u[:, :, None] * jnp.asarray(mask)[None, None, :]
        # x is (T, D_in, N): time is axis 0 here (D_in is a batch dim).
        return scan_mod.diag_scan_q(self.lam_q, x, nr, method=method,
                                    time_axis=0)

    def states_from_r(self, r_states, w_in_raw=None):
        """Theorem 5: r(t) = sum_d row_d(W_in) (.) row_d(R(t)) — apply W_in
        *after* the recurrence.  w_in_raw (D_in, N) real, un-leaked."""
        w_in = self.cfg.leak * jnp.asarray(
            self.w_in_raw if w_in_raw is None else w_in_raw)
        # Pack each W_in row like a coefficient vector: reals then (re, im)
        # pairs of [W_in]_P.  [W_in]_P = W_in P; its Q packing is exactly
        # W_in Q.
        win_q = w_in @ jnp.asarray(self.basis.q())  # (D_in, N)
        nr = self.n_real

        def one_row(rq_d, win_d):
            return scan_mod.realified_multiply(rq_d, win_d, nr)

        # r_states: (T, D_in, N); win_q: (D_in, N)
        contrib = jax.vmap(one_row, in_axes=(1, 0), out_axes=1)(r_states,
                                                                win_q)
        return contrib.sum(axis=1)
