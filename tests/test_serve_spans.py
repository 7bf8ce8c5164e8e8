"""The serving loop's ``serve.*`` host spans on the profiler's trace: every
span of the loop and the engine appears, children lie inside their
parents, the attributes join (a request's ``serve.submit`` to the
``serve.wave`` that admits it) and count what was served, and tracing
leaves the tokens bit-identical."""
import asyncio
import sys
from pathlib import Path

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.esn import ESNConfig, LinearESN
from repro.data.signals import mso_series
from repro.serve import OpenLoopServer, ReservoirEngine
from repro.serve.telemetry import annotate, span

CFG = ESNConfig(n=32, d_in=1, d_out=1, spectral_radius=0.9, leak=0.85,
                ridge_alpha=1e-6, seed=9)

LOOP_SPANS = {"serve.cycle", "serve.wait", "serve.submit", "serve.flush",
              "serve.decode", "serve.route", "serve.collect",
              "serve.release"}
ENGINE_SPANS = {"serve.plan", "serve.wave", "serve.dispatch", "serve.block"}

#: child -> the spans it must lie inside (any one of them).
PARENTS = {"serve.flush": {"serve.cycle"}, "serve.decode": {"serve.cycle"},
           "serve.route": {"serve.cycle"}, "serve.plan": {"serve.flush"},
           "serve.wave": {"serve.flush"},
           "serve.dispatch": {"serve.wave", "serve.decode"},
           "serve.block": {"serve.wave", "serve.decode", "serve.collect"},
           "serve.collect": {"serve.route"},
           "serve.release": {"serve.route"}}


@pytest.fixture(scope="module")
def model_and_signal():
    sig = mso_series(3, 1001)
    u, y = sig[:-1, None], sig[1:, None]
    return LinearESN.diagonalized(CFG).fit(u[:400], y[:400], washout=50), u


async def _tokens(server, handle):
    """The handle's tokens; raises what ended the serving loop, if the loop
    ended first."""
    got = asyncio.ensure_future(handle.tokens())
    await asyncio.wait([got, server._task],
                       return_when=asyncio.FIRST_COMPLETED)
    if not got.done():
        got.cancel()
        server._task.result()
        raise AssertionError("the serving loop ended before the stream")
    return got.result()


def _serve(model, u):
    """Three requests at once on two slots (one queues), then, after the
    loop has gone idle, a fourth; returns each request's tokens."""
    async def run():
        eng = ReservoirEngine(model, max_slots=2, decode_wave_tokens=3)
        server = OpenLoopServer(eng)
        await server.start()
        handles = [await server.submit(i, u[16 * i:16 * i + 32], n_decode=5)
                   for i in range(3)]
        toks = [await _tokens(server, h) for h in handles]
        await asyncio.sleep(0.02)
        late = await server.submit(3, u[100:140], n_decode=4)
        toks.append(await _tokens(server, late))
        await server.drain()
        return [np.stack([np.asarray(t.y) for t in ts]) for ts in toks]
    return asyncio.run(run())


def _spans(directory):
    """(name, start_ns, end_ns, attributes) of every ``serve.*`` event."""
    path = sorted(Path(directory).rglob("*.xplane.pb"))[-1]
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for plane in ProfileData.from_file(str(path)).planes
            for line in plane.lines for e in line.events
            if e.name.startswith("serve.")]


@pytest.fixture(scope="module")
def traced(model_and_signal, tmp_path_factory):
    model, u = model_and_signal
    directory = tmp_path_factory.mktemp("serve-trace")
    with jax.profiler.trace(str(directory)):
        tokens = _serve(model, u)
    return tokens, _spans(directory)


def _inside(child, parent) -> bool:
    return parent[1] <= child[1] and child[2] <= parent[2]


def test_every_span_appears(traced):
    _, spans = traced
    assert {s[0] for s in spans} == LOOP_SPANS | ENGINE_SPANS
    programs = {s[3]["program"] for s in spans if s[0] == "serve.dispatch"}
    assert programs == {"place_many", "prefill_wave", "closed_loop_fused"}


def test_children_lie_inside_their_parents(traced):
    _, spans = traced
    for child in spans:
        parents = PARENTS.get(child[0])
        if parents is None:
            continue
        assert any(_inside(child, p) for p in spans if p[0] in parents), child
    # A request that arrives while nothing is runnable lands in the wait.
    cycles = [s for s in spans if s[0] == "serve.cycle"]
    submits = [s for s in spans if s[0] == "serve.submit"]
    assert not any(_inside(s, c) for s in submits for c in cycles)
    late = next(s for s in submits if s[3]["sid"] == 3)
    assert any(_inside(late, w) for w in spans if w[0] == "serve.wait")


def test_submit_joins_the_wave_that_admits_it(traced):
    _, spans = traced
    waves = sorted((s for s in spans if s[0] == "serve.wave"),
                   key=lambda s: s[1])
    for sub in (s for s in spans if s[0] == "serve.submit"):
        sid = str(sub[3]["sid"])
        admitted = [w for w in waves
                    if sid in str(w[3]["sids"]).split("|")]
        assert len(admitted) == 1, sid
        assert admitted[0][1] >= sub[2]
        assert admitted[0][3]["t_bucket"] >= 32
    # Request 2 found both slots taken: it waits for a release.
    queued = next(w for w in waves if str(w[3]["sids"]) == "2")
    first_release = min(s[2] for s in spans if s[0] == "serve.release")
    assert queued[1] >= first_release


def test_route_tokens_sum_to_the_tokens_delivered(traced):
    tokens, spans = traced
    routes = [s[3] for s in spans if s[0] == "serve.route"]
    assert sum(r["tokens"] for r in routes) == sum(len(t) for t in tokens)
    assert all(0 <= r["unready"] <= r["sessions"] for r in routes)
    decoded = [s[3] for s in spans if s[0] == "serve.decode"]
    assert sum(d["rows"] * d["tokens"] for d in decoded) == sum(
        len(t) for t in tokens)


def test_collect_pulls_each_decode_wave_once(traced):
    """Every closed-loop wave's output crosses to the host in exactly one
    copy, made by the ``serve.collect`` that drains it; ``waited`` counts
    the copies whose wave was not yet computed, at most one per pull."""
    _, spans = traced
    collects = [s[3] for s in spans if s[0] == "serve.collect"]
    waves = [s for s in spans if s[0] == "serve.dispatch"
             and s[3]["program"] == "closed_loop_fused"]
    assert sum(c["pulls"] for c in collects) == len(waves) > 0
    assert all(0 <= c["waited"] <= c["pulls"] <= 1 for c in collects)


@pytest.mark.parametrize("d", [1, 8])
def test_collect_bytes_are_the_whole_wave_output(tmp_path, d):
    """``serve.collect``'s ``bytes`` counts what its pulls copied: one
    request of K tokens is one wave, and its copy is the whole
    ``(K, max_slots, D)`` output, empty slots included."""
    k, slots = 3, 2
    cfg = ESNConfig(n=32, d_in=d, d_out=d, spectral_radius=0.9, leak=0.85,
                    ridge_alpha=1e-6, seed=9)
    sig = mso_series(3, 1001)
    sig = np.stack([np.roll(sig, 7 * j) for j in range(d)], axis=1)
    u, y = sig[:-1], sig[1:]
    model = LinearESN.diagonalized(cfg).fit(u[:400], y[:400], washout=50)

    async def run():
        server = OpenLoopServer(ReservoirEngine(
            model, max_slots=slots, decode_wave_tokens=k))
        await server.start()
        toks = await _tokens(server, await server.submit(
            0, u[:32], n_decode=k))
        await server.drain()
        return toks

    with jax.profiler.trace(str(tmp_path)):
        toks = asyncio.run(run())
    assert len(toks) == k and np.asarray(toks[0].y).shape == (d,)
    collects = [s[3] for s in _spans(tmp_path) if s[0] == "serve.collect"]
    (pulled,) = [c for c in collects if c["pulls"]]
    itemsize = np.asarray(toks[0].y).dtype.itemsize
    assert pulled["pulls"] == 1
    assert pulled["bytes"] == k * slots * d * itemsize
    assert all(c["bytes"] == 0 for c in collects if not c["pulls"])


def test_placement_reports_the_releases_it_folds_in(model_and_signal,
                                                    tmp_path):
    """A release makes no device call; the next placement writes the
    arena's occupancy mirror, and its ``serve.dispatch`` reports how many
    releases that write folds in (``freed``)."""
    model, u = model_and_signal
    eng = ReservoirEngine(model, max_slots=2)
    with jax.profiler.trace(str(tmp_path)):
        eng.submit(0, u[:32])
        eng.submit(1, u[32:64])
        eng.flush()
        eng.release(0, drop=True)
        eng.release(1, drop=True)
        eng.submit(2, u[64:96])
        eng.flush()
    places = sorted((s for s in _spans(tmp_path) if s[0] == "serve.dispatch"
                     and s[3]["program"] == "place_many"),
                    key=lambda s: s[1])
    assert [s[3]["freed"] for s in places] == [0, 2]
    np.testing.assert_array_equal(np.asarray(eng._exec.arena.active),
                                  [True, False])


def test_routing_touches_no_device_array(model_and_signal, monkeypatch):
    """The front end routes host arrays: with a device array's
    ``__iter__`` and ``__getitem__`` raising inside ``_route_tokens``, a
    whole open-loop run still streams every token, bit-identical."""
    plain = _serve(*model_and_signal)
    array_type = type(jax.numpy.zeros(()))
    route = OpenLoopServer._route_tokens

    def boom(*args, **kw):
        raise AssertionError("device array sliced while routing")

    def guarded(server):
        with monkeypatch.context() as m:
            m.setattr(array_type, "__iter__", boom)
            m.setattr(array_type, "__getitem__", boom)
            return route(server)
    monkeypatch.setattr(OpenLoopServer, "_route_tokens", guarded)
    got = _serve(*model_and_signal)
    assert [len(t) for t in got] == [5, 5, 5, 4]
    for a, b in zip(plain, got):
        np.testing.assert_array_equal(a, b)


def test_tracing_leaves_the_tokens_bit_identical(traced, model_and_signal):
    tokens, _ = traced
    plain = _serve(*model_and_signal)
    assert len(plain) == len(tokens)
    for a, b in zip(plain, tokens):
        np.testing.assert_array_equal(a, b)


def test_span_attributes_only_while_tracing(tmp_path):
    def boom():
        raise AssertionError("built with no trace running")
    assert not TraceAnnotation.is_enabled()
    with span("serve.x", a=boom) as s:
        annotate(s, b=boom)
    with jax.profiler.trace(str(tmp_path)):
        with span("serve.x", sids=["a,b", 3], f=lambda: 7, g="p#q") as s:
            annotate(s, n=2)
    (got,) = _spans(tmp_path)
    # The trace's metadata ends a value at "," and "#": they become ";".
    assert got[3] == {"sids": "a;b|3", "f": 7, "g": "p;q", "n": 2}


def test_serve_cli_profile_dir_holds_the_spans(tmp_path, monkeypatch):
    from repro.launch import runtime, serve
    monkeypatch.setattr(runtime, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(sys, "argv", [
        "serve", "--reservoir", "--sessions", "3", "--slots", "2",
        "--prompt-len", "40", "--gen", "4", "--n", "32",
        "--profile-dir", str(tmp_path)])
    serve.main()
    names = {s[0] for s in _spans(tmp_path)}
    assert {"serve.plan", "serve.wave", "serve.dispatch"} <= names
