"""Releasing a session makes no device call: the slot arena's ``active``
occupancy mirror is written whole from the host slot table by the next
placement (``arena.place_many``) and by every read through
``ReservoirEngine.arena``, never by the release itself.  Each test runs on
the synchronous executor and on the pipelined one."""
import jax
import numpy as np
import pytest

from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.data.signals import mso_series
from repro.serve import ReservoirEngine, arena as arena_mod

CFG = ESNConfig(n=32, d_in=1, d_out=1, spectral_radius=0.9, leak=0.85,
                input_scaling=0.5, ridge_alpha=1e-8, seed=11)
DEPTHS = pytest.mark.parametrize("depth", [0, 2])


@pytest.fixture(scope="module")
def fitted():
    sig = mso_series(3, 601)
    u, y = sig[:-1, None], sig[1:, None]
    params = esn_fn.diag_params(CFG)
    return params, esn_fn.fit(params, u[:400], y[:400], washout=50), u


def _engine(fitted, depth, sids, slots=3):
    """An engine with ``sids`` admitted, prefilled and decoded 4 tokens,
    every token collected."""
    params, readout, u = fitted
    eng = ReservoirEngine(params, max_slots=slots, readout=readout,
                          pipeline_depth=depth)
    for i, sid in enumerate(sids):
        eng.submit(sid, u[20 * i:20 * i + 40])
    eng.flush()
    eng.decode_closed_loop(4)
    eng.collect_decoded()
    return eng


def _occupancy(eng):
    return np.array([s is not None for s in eng._table.slots], bool)


@DEPTHS
def test_release_makes_no_device_call(fitted, depth, monkeypatch):
    eng = _engine(fitted, depth, "abc")
    plane = eng._exec
    calls = []

    def counted(name, fn):
        def call(*args, **kw):
            calls.append(name)
            return fn(*args, **kw)
        return call
    for name in [n for n in vars(plane) if n.endswith("_jit")]:
        monkeypatch.setattr(plane, name, counted(name, getattr(plane, name)))
    for name in arena_mod.__all__:
        fn = getattr(arena_mod, name)
        if callable(fn) and not isinstance(fn, type):
            monkeypatch.setattr(arena_mod, name, counted(name, fn))
    before = plane.arena
    with jax.transfer_guard("disallow_explicit"):
        out = eng.release("b", drop=True)
    assert calls == []
    assert plane.arena is before
    assert out.state is None and "b" not in eng.sessions


@DEPTHS
def test_readmission_writes_the_whole_mirror(fitted, depth):
    """Two releases, then one re-admission: the placement that re-uses a
    freed slot marks the other freed slot free as well, and the new
    session decodes as on a fresh engine, bit for bit."""
    params, readout, u = fitted
    eng = _engine(fitted, depth, "abc")
    slot_a = eng.sessions["a"].slot
    eng.release("a", drop=True)
    eng.release("c", drop=True)
    eng.submit("d", u[200:260])
    eng.flush()
    assert eng.sessions["d"].slot == slot_a
    occupied = _occupancy(eng)
    assert occupied.tolist() == [True, True, False]
    # The placement wrote it: the plane's own arena, read before any read
    # through the engine could write it.
    np.testing.assert_array_equal(np.asarray(eng._exec.arena.active),
                                  occupied)
    np.testing.assert_array_equal(np.asarray(eng.arena.active), occupied)
    got = eng.decode_closed_loop(6, sids=["d"])["d"]

    fresh = ReservoirEngine(params, max_slots=3, readout=readout,
                            pipeline_depth=depth)
    fresh.submit("d", u[200:260])
    fresh.flush()
    assert fresh.sessions["d"].slot == slot_a
    np.testing.assert_array_equal(
        got, fresh.decode_closed_loop(6, sids=["d"])["d"])


@DEPTHS
def test_engine_read_after_release_matches_the_table(fitted, depth):
    eng = _engine(fitted, depth, "abc")
    eng.release("b", drop=True)
    occupied = _occupancy(eng)
    assert occupied.tolist() == [True, False, True]
    np.testing.assert_array_equal(np.asarray(eng.arena.active), occupied)
    np.testing.assert_array_equal(np.asarray(eng._exec.arena.active),
                                  occupied)


@DEPTHS
def test_snapshot_after_release_round_trips_the_occupancy(fitted, depth,
                                                          tmp_path):
    eng = _engine(fitted, depth, "abc")
    eng.release("b", drop=True)
    occupied = _occupancy(eng)
    path = eng.snapshot(str(tmp_path / "snap"))
    with np.load(f"{path}/arrays.npz") as data:
        np.testing.assert_array_equal(data["arena/active"], occupied)
    back = ReservoirEngine.restore(path)
    np.testing.assert_array_equal(np.asarray(back.arena.active), occupied)
    assert sorted(back.sessions) == ["a", "c"]
    want = eng.decode_closed_loop(5)
    got = back.decode_closed_loop(5)
    for sid in ("a", "c"):
        np.testing.assert_array_equal(got[sid], want[sid])


@DEPTHS
def test_release_keeping_the_state_returns_the_slots_row(fitted, depth):
    """``drop=False`` hands back the slot's row as it was at the release,
    and a later placement into the slot does not change what was handed
    back."""
    _, _, u = fitted
    eng = _engine(fitted, depth, "abc")
    slot = eng.sessions["b"].slot
    h_want = eng.state_of("b")
    y_want = np.asarray(eng._exec.arena.y_prev[slot])
    state, y = eng.release("b")
    eng.submit("d", u[300:340])
    eng.flush()
    assert eng.sessions["d"].slot == slot
    np.testing.assert_array_equal(np.asarray(state), h_want)
    np.testing.assert_array_equal(np.asarray(y), y_want)
    assert not np.array_equal(eng.state_of("d"), h_want)
