"""The plane split, pinned: import layering, facade parity, SLO fairness,
driven interleave, and the asyncio open-loop front end.

The acceptance bar for the engine-monolith split:

* one-way imports — planes never import each other sideways or upward
  (module-level regex over the plane sources, the PR-2 layering idiom);
* the facade forwards no attribute (no ``__getattr__``): its public
  surface is its own members, and the session store imports no serve
  module at all;
* the facade is *thin* (``serve/engine.py`` stays under 700 lines) and
  *bit-exact*: replaying the recorded mixed workload through the public
  surface reproduces the pre-refactor reference outputs <= 1e-5;
* per-session decode SLOs: a premium session's tighter deadline decodes
  first and cannot be starved by default-tier prefill traffic;
* ``queue_inputs`` + interleaved flush advances a session bit-identically
  to the same rows fed one at a time through ``decode_step``;
* the ``OpenLoopServer`` streams per-token, surfaces ``AdmissionFull`` as
  backpressure, and drains gracefully.
"""
import asyncio
import pathlib
import re
import sys

import jax
import numpy as np
import pytest

from repro.core.esn import ESNConfig, LinearESN
from repro.data.signals import mso_series
from repro.serve import (AdmissionFull, OpenLoopServer, ReservoirEngine,
                         Tracker)
from repro.serve.cost import WaveCostModel, cost_key

sys.path.insert(0, str(pathlib.Path(__file__).parent))       # workload module
from facade_parity_workload import REF_PATH, run_workload    # noqa: E402

import repro.serve as serve_pkg  # noqa: E402

SERVE_DIR = pathlib.Path(serve_pkg.__file__).parent

CFG = ESNConfig(n=32, d_in=1, d_out=1, spectral_radius=0.9, leak=0.85,
                ridge_alpha=1e-6, seed=9)


def _fitted(cfg=CFG, t=1001):
    sig = mso_series(3, t)
    u, y = sig[:-1, None], sig[1:, None]
    model = LinearESN.diagonalized(cfg).fit(u[:400], y[:400], washout=50)
    return model, u, y


def _cost_model(cfg=CFG):
    return WaveCostModel(key=cost_key(jax.default_backend(), cfg.n,
                                      cfg.d_out))


class _RecTracker(Tracker):
    """Records every plane event — the observability seam as a test probe."""

    def __init__(self):
        self.events = []

    def log_wave(self, event: dict) -> None:
        self.events.append(dict(event))


# ------------------------------------------------------------ import layering
#: module -> serve-sibling modules it must NEVER import at module level.
#: Planes import only downward (telemetry / infra), never each other; the
#: facade never imports the front end.  Function-level (indented) lazy
#: imports are the sanctioned escape hatch and deliberately pass.
_FORBIDDEN = {
    "telemetry.py": {"arena", "cost", "scheduler", "store", "ingest",
                     "exec_plane", "learn", "engine", "frontend"},
    "arena.py": {"ingest", "exec_plane", "learn", "engine", "frontend"},
    "cost.py": {"ingest", "exec_plane", "learn", "engine", "frontend"},
    "scheduler.py": {"ingest", "exec_plane", "learn", "engine", "frontend"},
    "store.py": {"ingest", "exec_plane", "learn", "engine", "frontend"},
    "ingest.py": {"exec_plane", "learn", "engine", "frontend"},
    "exec_plane.py": {"ingest", "learn", "engine", "frontend"},
    "learn.py": {"ingest", "exec_plane", "engine", "frontend"},
    "engine.py": {"frontend"},
    "snapshot.py": {"frontend"},
    "frontend.py": {"exec_plane", "learn", "engine", "arena", "store",
                    "scheduler", "cost"},
}


def test_plane_imports_are_one_way():
    for fname, banned in _FORBIDDEN.items():
        src = (SERVE_DIR / fname).read_text()
        for mod in banned:
            pat = re.compile(
                rf"^(from|import)\s+[.\w]*\b{mod}\b", re.MULTILINE)
            m = pat.search(src)
            assert m is None, (
                f"{fname} imports sibling {mod!r} at module level: "
                f"{m.group(0)!r} — planes talk through facade-wired "
                f"callbacks, not imports")


def test_store_imports_no_serve_sibling():
    """The store (the paging tier) imports no serve module, at any
    indentation: the snapshot code that reached up into every plane lives
    in ``snapshot.py``."""
    src = (SERVE_DIR / "store.py").read_text()
    m = re.search(r"^\s*(from\s+\.|(from|import)\s+repro\.serve\b)", src,
                  re.MULTILINE)
    assert m is None, f"store.py imports a serve module: {m.group(0)!r}"


#: the facade's public surface that its planes own.
_PLANE_SURFACE = ("sessions", "active_sessions", "ready_sessions",
                  "free_slots", "max_queued", "queue_inputs", "input_depth",
                  "pop_inputs", "state_of", "decode_step", "observe",
                  "decode_closed_loop", "collect_decoded", "drift_rmse",
                  "clear_decode_gaps")


def test_facade_forwards_nothing_and_names_its_surface():
    """No ``__getattr__`` on the facade: each public name is a member of
    it, and a private plane name does not resolve on an engine."""
    assert "__getattr__" not in vars(ReservoirEngine)
    model, _, _ = _fitted()
    eng = ReservoirEngine(model, max_slots=2, max_queued=3)
    for name in _PLANE_SURFACE:
        assert hasattr(eng, name), name
    assert eng.max_queued == 3 and eng.free_slots == 2
    assert eng.sessions == {} and eng.ready_sessions == []
    for name in ("_decode_buf", "_inflight", "_learn_state", "_slots",
                 "pipeline_depth"):
        assert not hasattr(eng, name), name


def test_facade_is_thin():
    n_lines = len((SERVE_DIR / "engine.py").read_text().splitlines())
    assert n_lines < 700, (
        f"serve/engine.py has {n_lines} lines — the facade must stay thin; "
        f"move logic into the owning plane")


# ------------------------------------------------------------- facade parity
def test_facade_replays_prerefactor_outputs():
    """The recorded mixed workload (churn, chunked prefill, streaming
    learn + refit, paging, release/re-admit, decode) through the public
    surface must reproduce the monolith-era reference bit-for-bit-ish
    (<= 1e-5; NaN patterns must match exactly)."""
    got = run_workload()
    ref = np.load(REF_PATH)
    assert set(got) == set(ref.files), sorted(set(got) ^ set(ref.files))
    for k in ref.files:
        a = np.asarray(got[k], dtype=float)
        b = np.asarray(ref[k], dtype=float)
        assert a.shape == b.shape, (k, a.shape, b.shape)
        na, nb = np.isnan(a), np.isnan(b)
        assert (na == nb).all(), f"{k}: NaN pattern diverged"
        if (~na).any():
            np.testing.assert_allclose(a[~na], b[~nb], rtol=0, atol=1e-5,
                                       err_msg=k)


# ------------------------------------------------- per-session SLO fairness
def test_premium_slo_decodes_before_default_tier():
    """Starvation bound: under a flood of default-tier prefill traffic, a
    premium session (tight per-request ``decode_slo_us``) gets decode
    waves interleaved before the prefill queue drains, while a session
    with a huge deadline never becomes due."""
    model, u, _ = _fitted()
    rec = _RecTracker()
    eng = ReservoirEngine(model, max_slots=4, cost_model=_cost_model(),
                          decode_wave_tokens=1, tracker=rec)
    eng.submit("prem", u[:33], decode_slo_us=1.0)       # due immediately
    eng.submit("std", u[40:73], decode_slo_us=1e12)     # never due
    eng.flush()
    rec.events.clear()
    # Default-tier flood: distinct buckets force several prefill waves.
    for i, t in enumerate([17, 33, 65, 90, 120, 150]):
        eng.submit(f"flood{i}", u[i:i + t])
    eng.flush(decode_interleave=True, decode_sids=["prem", "std"])

    kinds = [(e["kind"], e.get("mode"), e.get("sids")) for e in rec.events]
    decoded = [e for e in rec.events
               if e["kind"] == "decode" and e.get("mode") == "interleave"]
    assert decoded, f"no interleaved decode wave ran: {kinds}"
    assert any("prem" in e["sids"] for e in decoded)
    assert all("std" not in e["sids"] for e in decoded), (
        "a deadline of 1e12us became due — per-session SLOs leaked")
    first_prem = min(i for i, e in enumerate(rec.events)
                     if e["kind"] == "decode" and "prem" in e["sids"])
    last_prefill = max(i for i, e in enumerate(rec.events)
                       if e["kind"] == "prefill")
    assert first_prem < last_prefill, (
        "premium session starved: first decode wave only ran after the "
        "entire default-tier prefill queue drained")


def test_submit_slo_must_be_positive():
    model, u, _ = _fitted()
    eng = ReservoirEngine(model, max_slots=2)
    with pytest.raises(ValueError, match="decode_slo_us"):
        eng.submit("s", u[:20], decode_slo_us=0.0)


# --------------------------------------------- driven interleave bit-parity
def test_queued_inputs_interleave_matches_decode_step():
    """Rows buffered via ``queue_inputs`` and drained by an interleaved
    flush advance the session bit-identically to feeding the same rows
    through ``decode_step`` one at a time."""
    model, u, _ = _fitted()
    rows = [u[500 + i] for i in range(4)]

    eng_a = ReservoirEngine(model, max_slots=4, cost_model=_cost_model(),
                            decode_wave_tokens=2)
    eng_a.submit("s", u[:33], decode_slo_us=1.0)
    eng_a.flush()
    eng_a.collect_decoded()
    eng_a.queue_inputs("s", np.stack(rows))
    for i, t in enumerate([17, 65, 120]):               # several buckets
        eng_a.submit(f"f{i}", u[i:i + t])
    eng_a.flush(decode_interleave=True, decode_sids=["s"])
    got = eng_a.collect_decoded("s").tokens["s"]
    assert len(got) >= 2, "interleaved flush never drove the session"

    eng_b = ReservoirEngine(model, max_slots=4)
    eng_b.submit("s", u[:33])
    eng_b.flush()
    eng_b.collect_decoded()
    for r in rows[:len(got)]:
        eng_b.decode_step({"s": r})
    want = eng_b.collect_decoded("s").tokens["s"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(eng_a.state_of("s")),
                                  np.asarray(eng_b.state_of("s")))


# ------------------------------------------------------- open-loop front end
def test_frontend_streams_per_token():
    model, u, _ = _fitted()

    async def run():
        eng = ReservoirEngine(model, max_slots=2)
        server = OpenLoopServer(eng)
        await server.start()
        h1 = await server.submit("a", u[:32], n_decode=3)
        h2 = await server.submit("b", u[16:48], n_decode=3)
        toks1 = await h1.tokens()
        toks2 = await h2.tokens()
        await server.drain()
        return eng, h1, h2, toks1, toks2

    eng, h1, h2, toks1, toks2 = asyncio.run(run())
    for h, toks in ((h1, toks1), (h2, toks2)):
        assert [t.index for t in toks] == [0, 1, 2]
        assert all(t.y.shape == (1,) for t in toks)
        walls = [t.t_wall for t in toks]
        assert walls == sorted(walls)
        assert h.t_admitted is not None and h.t_first is not None
        assert h.t_done >= h.t_first >= h.t_admitted
    # Finished sessions were released — the engine is empty again.
    assert not eng.sessions and len(eng.scheduler) == 0


def test_frontend_surfaces_admission_backpressure():
    model, u, _ = _fitted()

    async def run():
        eng = ReservoirEngine(model, max_slots=1, max_queued=1)
        server = OpenLoopServer(eng)          # loop not started: no drain
        await server.submit("a", u[:32], n_decode=1)
        with pytest.raises(AdmissionFull):
            await server.submit("b", u[:32], n_decode=1)
        assert "b" not in server._sessions    # nothing half-registered
        await server.abort()

    asyncio.run(run())


def test_frontend_graceful_drain():
    model, u, _ = _fitted()

    async def run():
        eng = ReservoirEngine(model, max_slots=2)
        server = OpenLoopServer(eng)
        await server.start()
        h = await server.submit("a", u[:32], n_decode=2)
        await server.drain()                  # serves in-flight to quota
        toks = await h.tokens()
        assert len(toks) == 2                 # stream completed, not cut
        with pytest.raises(RuntimeError, match="draining"):
            await server.submit("late", u[:32])
        assert not eng.sessions and len(eng.scheduler) == 0
        return True

    assert asyncio.run(run())


def test_frontend_emits_tracker_events():
    model, u, _ = _fitted()
    rec = _RecTracker()

    async def run():
        eng = ReservoirEngine(model, max_slots=2, tracker=rec)
        server = OpenLoopServer(eng)
        await server.start()
        await server.submit("a", u[:32], n_decode=2)
        await server.drain()

    asyncio.run(run())
    fe = [e for e in rec.events if e["kind"] == "frontend"]
    assert len(fe) == 1 and fe[0]["sid"] == "a" and fe[0]["tokens"] == 2
    assert fe[0]["ttft_s"] > 0 and fe[0]["e2e_s"] >= fe[0]["ttft_s"]


# -------------------------------------------------- loadgen distributions
def test_loadgen_distributions():
    repo = pathlib.Path(__file__).parent.parent
    sys.path.insert(0, str(repo))
    from benchmarks.loadgen import (bursty_arrivals, pareto_lengths,
                                    poisson_arrivals)
    rng = np.random.default_rng(0)
    for fn in (poisson_arrivals, bursty_arrivals):
        arr = fn(rng, 8.0, 500)
        assert arr.shape == (500,)
        assert (np.diff(arr) >= 0).all() and arr[0] > 0
    lens = pareto_lengths(rng, 2000, xm=12, cap=192)
    assert lens.min() >= 12 and lens.max() <= 192
    assert np.issubdtype(lens.dtype, np.integer)
    assert np.mean(lens) > 12            # heavy tail actually present
