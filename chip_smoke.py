"""Bring-up smoke: the reservoir serving engine's main path on one TPU chip.

    python chip_smoke.py [--seed S]      # one chip (what CI-style checks run)
    python chip_smoke.py --chips 4       # sharded arena on a 4x1 mesh vs one chip

The deployment is the repo's own MSO forecasting engine at full width:
``ESNConfig(n=1024, d_in=1, d_out=1)``, DPG ``noisy_golden`` params from
``--seed``, a ridge readout fit on the host in float64, and
``ReservoirEngine(max_slots=64)`` serving in float32.  One chip runs:

1. compile — the long-prompt prefill wave (64 rows x 1024 steps, the Pallas
   scan) and the closed-loop decode (fused Pallas kernel, K=8); both
   compiled texts must hold a ``tpu_custom_call``;
2. long prompts — 64 sessions x 1024 steps in one prefill wave, then 64
   tokens each in closed loop, 8 fused waves of K=8;
3. short prompts — 64 sessions x 16 steps (the sequential scan), 64 tokens;
4. front end — 16 requests through ``OpenLoopServer``, 16 tokens each.

Every state and output must be finite and agree with a plain reference: a
numpy float64 loop of h_t = lambda * h_{t-1} + W_in u_t plus the readout,
on the host, written here and independent of ``repro.core``.  Any failure
exits non-zero before the last line; the last line on success is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
Runs in one process and starts no other.  The compile cache is
``JAX_COMPILATION_CACHE_DIR`` when set, else ``.jax_cache/`` here.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

N, SLOTS, K, TOKENS = 1024, 64, 8, 64
LONG_T, SHORT_T = 1024, 16
FRONTEND_REQUESTS, FRONTEND_TOKENS = 16, 16
# Tolerances against the float64 reference.  The device runs float32
# (rounding u = 6e-8 per operation); the recurrence contracts at
# max|lambda| ~ 0.956, so a state carries at most ~u / (1 - 0.956) ~ 1.4e-6
# of its scale in accumulated rounding.  States must agree to STATE_RTOL of
# the largest reference state.  An output sums 1025 weighted features, so
# its error scales with sum|w_out| * max|h|: outputs, through 64 closed-loop
# tokens, must agree to OUT_RTOL of that scale.  One bfloat16 pass anywhere
# on the path (rounding 4e-3) would miss both by two orders of magnitude.
STATE_RTOL = 1e-5
OUT_RTOL = 1e-5
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(Exception):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def require_tpu(chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    print(f"device: platform={dev['platform']} kind={dev['kind']} "
          f"count={dev['count']}", flush=True)
    check(dev["platform"] == "tpu", f"no TPU: JAX runs on {dev['platform']}")
    check(dev["count"] >= chips, f"need {chips} chips, have {dev['count']}")
    return dev


# ------------------------------------------------------------------ reference
def _complex_lanes(v, nr):
    """Packed layout ``[reals | re0, im0, re1, im1, ...]`` -> complex lanes."""
    return np.concatenate([v[..., :nr], v[..., nr::2] + 1j * v[..., nr + 1::2]],
                          axis=-1)


def _packed(c, nr):
    out = np.empty(c.shape[:-1] + (2 * c.shape[-1] - nr,))
    out[..., :nr] = c[..., :nr].real
    out[..., nr::2] = c[..., nr:].real
    out[..., nr + 1::2] = c[..., nr:].imag
    return out


class Reference:
    """h_t = lambda * h_{t-1} + W_in u_t and y_t = b + h_t . W_h in numpy
    float64, from the engine's own (float32) arrays.  The state is complex
    per eigenvalue; the engine stores it in the packed real layout."""

    def __init__(self, params, w_out):
        cfg = params.cfg
        check(cfg.use_bias and not cfg.use_feedback,
              "reference covers bias-on, feedback-off configurations")
        self.nr = params.n_real
        self.lam = _complex_lanes(np.asarray(params.lam_q, np.float64), self.nr)
        self.win = _complex_lanes(np.asarray(params.win_q, np.float64), self.nr)
        w = np.asarray(w_out, np.float64)
        self.b, self.wh = w[0], w[1:]
        self.scale_w = float(np.abs(w).sum(axis=0).max())

    def prefill(self, u):
        """u (B, T, D_in) -> final complex states (B, NC)."""
        h = np.zeros((u.shape[0], self.lam.shape[0]), complex)
        for t in range(u.shape[1]):
            h = self.lam * h + u[:, t] @ self.win
        return h

    def readout(self, h):
        return self.b + _packed(h, self.nr) @ self.wh

    def closed_loop(self, h, n):
        """n free-running tokens after a prefill; (B, n, D_out)."""
        y = self.readout(h)
        ys = []
        for _ in range(n):
            h = self.lam * h + y @ self.win
            y = self.readout(h)
            ys.append(y)
        return np.stack(ys, axis=1)

    def packed(self, h):
        return _packed(h, self.nr)


def compare(name, got, want, scale, rtol) -> float:
    got = np.asarray(got, np.float64)
    check(got.shape == want.shape, f"{name}: shape {got.shape} != {want.shape}")
    check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
    err = float(np.abs(got - want).max()) / scale
    print(f"  {name}: max error {err:.3e} of scale {scale:.3e} "
          f"(tolerance {rtol:.0e})", flush=True)
    check(err <= rtol, f"{name}: error {err:.3e} > {rtol:.0e}")
    return err


# --------------------------------------------------------------------- phases
def compile_phase(eng) -> None:
    """AOT-compile the long prefill wave and the closed-loop decode as the
    engine calls them; each must contain the Pallas kernel."""
    import jax.numpy as jnp
    from repro.core import dispatch
    ex = eng._exec
    method = dispatch.resolve_method(LONG_T)
    check(method == "pallas", f"{LONG_T}-step wave resolved to {method!r}")
    check(dispatch.resolve_decode_method() == "pallas",
          "closed-loop decode did not resolve to the Pallas kernel")
    dt = eng.params.dtype
    wave = ex._wave_jit.lower(
        eng.params, ex._wave_w(), eng.arena,
        jnp.arange(SLOTS, dtype=jnp.int32),
        jnp.zeros((SLOTS, LONG_T, 1), dt),
        jnp.full((SLOTS,), LONG_T, jnp.int32), None,
        method=method, chunk=128, want_outputs=False)
    closed = ex._closed_jit.lower(
        eng.params, ex._wave_w(), eng.arena, jnp.ones((SLOTS,), bool), K,
        None)
    for name, lowered in (("prefill_wave[64x1024]", wave),
                          ("closed_loop[K=8]", closed)):
        t0 = time.perf_counter()
        text = lowered.compile().as_text()
        sec = time.perf_counter() - t0
        print(f"compile {name}: {sec:.2f} s, tpu_custom_call="
              f"{'tpu_custom_call' in text}", flush=True)
        check("tpu_custom_call" in text, f"{name} holds no Pallas kernel")


def serve_round(eng, ref, prompts, label):
    """Submit one session per slot, flush, decode TOKENS in K-token waves;
    check states and outputs against the reference; release."""
    sids = [(label, i) for i in range(len(prompts))]
    for sid, u in zip(sids, prompts):
        eng.submit(sid, u)
    eng.flush()
    slots = [eng.sessions[s].slot for s in sids]
    h_ref = ref.prefill(prompts.astype(np.float64))
    compare(f"{label} prefill states", np.asarray(eng.states)[slots],
            ref.packed(h_ref), float(np.abs(ref.packed(h_ref)).max()),
            STATE_RTOL)
    for _ in range(TOKENS // K):
        eng.decode_closed_loop(K, sids=sids)
    got = eng.collect_decoded()
    ys = np.stack([np.asarray(got[s]) for s in sids])
    want = ref.closed_loop(h_ref, TOKENS)
    compare(f"{label} closed-loop outputs", ys, want,
            ref.scale_w * float(np.abs(ref.packed(h_ref)).max()), OUT_RTOL)
    for s in sids:
        eng.release(s, drop=True)
    return len(sids) * (prompts.shape[1] + TOKENS)


def frontend_phase(eng, ref, prompts) -> int:
    from repro.serve.frontend import OpenLoopServer

    async def run():
        server = OpenLoopServer(eng)
        await server.start()
        handles = [await server.submit(("fe", i), u, n_decode=FRONTEND_TOKENS)
                   for i, u in enumerate(prompts)]
        toks = [await h.tokens() for h in handles]
        await server.drain()
        return toks

    toks = asyncio.run(run())
    check(all(len(t) == FRONTEND_TOKENS for t in toks),
          f"front end answered {[len(t) for t in toks]} tokens")
    ys = np.stack([np.stack([np.asarray(tok.y) for tok in t]) for t in toks])
    h_ref = ref.prefill(prompts.astype(np.float64))
    compare("front-end outputs", ys, ref.closed_loop(h_ref, FRONTEND_TOKENS),
            ref.scale_w * float(np.abs(ref.packed(h_ref)).max()), OUT_RTOL)
    print(f"  front end: {len(toks)} requests answered", flush=True)
    return len(toks) * (prompts.shape[1] + FRONTEND_TOKENS)


def windows(sig, rng, rows, t):
    starts = rng.integers(0, len(sig) - t, size=rows)
    return np.stack([sig[s:s + t, None] for s in starts]).astype(np.float32)


def one_chip(seed: int) -> None:
    from repro.launch.serve import build_reservoir_engine, mso_deployment
    cfg, sig = mso_deployment(N, seed)
    eng = build_reservoir_engine(cfg, sig, slots=SLOTS, decode_wave_tokens=K)
    check(eng.params.dtype == np.float32, f"engine dtype {eng.params.dtype}")
    ref = Reference(eng.params, eng.w_out)
    rng = np.random.default_rng(seed)
    compile_phase(eng)
    tokens = serve_round(eng, ref, windows(sig, rng, SLOTS, LONG_T), "long")
    tokens += serve_round(eng, ref, windows(sig, rng, SLOTS, SHORT_T), "short")
    tokens += frontend_phase(eng, ref,
                             windows(sig, rng, FRONTEND_REQUESTS, SHORT_T))
    print(f"served: {SLOTS} slots x N={N}, {tokens} tokens "
          f"(prefill + decode)", flush=True)


def four_chips(seed: int) -> None:
    """The same sessions on a 4x1 mesh (slots data-parallel) and on one
    chip: outputs agree, and the arena is spread over four devices."""
    from repro.launch.mesh import make_local_mesh
    from repro.launch.serve import build_reservoir_engine, mso_deployment
    cfg, sig = mso_deployment(N, seed)
    prompts = windows(sig, np.random.default_rng(seed), SLOTS, LONG_T)
    runs = {}
    for label, mesh in (("mesh4x1", make_local_mesh(4, 1)), ("one", None)):
        eng = build_reservoir_engine(cfg, sig, slots=SLOTS,
                                     decode_wave_tokens=K, mesh=mesh)
        n_dev = len(eng.arena.states.sharding.device_set)
        print(f"{label}: arena states on {n_dev} device(s)", flush=True)
        check(n_dev == (4 if mesh is not None else 1),
              f"{label}: arena on {n_dev} devices")
        sids = list(range(SLOTS))
        for sid, u in zip(sids, prompts):
            eng.submit(sid, u)
        eng.flush()
        slots = [eng.sessions[s].slot for s in sids]
        states = np.asarray(eng.states)[slots]
        for _ in range(TOKENS // K):
            eng.decode_closed_loop(K, sids=sids)
        got = eng.collect_decoded()
        runs[label] = (states, np.stack([np.asarray(got[s]) for s in sids]))
        ref = Reference(eng.params, eng.w_out)
    h_ref = ref.prefill(prompts.astype(np.float64))
    h_scale = float(np.abs(ref.packed(h_ref)).max())
    y_scale = ref.scale_w * h_scale
    want = ref.closed_loop(h_ref, TOKENS)
    for label, (states, ys) in runs.items():
        compare(f"{label} prefill states", states, ref.packed(h_ref), h_scale,
                STATE_RTOL)
        compare(f"{label} closed-loop outputs", ys, want, y_scale, OUT_RTOL)
    compare("mesh4x1 vs one-chip states", runs["mesh4x1"][0],
            runs["one"][0].astype(np.float64), h_scale, STATE_RTOL)
    compare("mesh4x1 vs one-chip outputs", runs["mesh4x1"][1],
            runs["one"][1].astype(np.float64), y_scale, OUT_RTOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4x1 sharded-arena comparison")
    args = ap.parse_args(argv)
    from repro.launch.runtime import enable_compile_cache
    compile_log = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, sec, **kw: compile_log.append(
            (kw.get("fun_name", "?"), sec)) if event == COMPILE_EVENT
        else None)
    try:
        dev = require_tpu(args.chips)
        print(f"compile cache: {enable_compile_cache()}", flush=True)
        t0 = time.perf_counter()
        if args.chips == 4:
            four_chips(args.seed)
        else:
            one_chip(args.seed)
        total = sum(sec for _, sec in compile_log)
        print(f"compile seconds: {total:.2f} over {len(compile_log)} "
              f"executables; wall {time.perf_counter() - t0:.1f} s",
              flush=True)
        for name, sec in sorted(compile_log, key=lambda r: -r[1])[:8]:
            print(f"  {sec:8.2f} s  {name}", flush=True)
    except SmokeFailure as e:
        print(f"chip_smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
