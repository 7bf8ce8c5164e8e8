"""Ahead-of-time compiles of the serving path for a TPU v5e that is
described, not attached: the Pallas kernels at deployment width and the
engine's jitted prefill-wave and closed-loop executables.  Each must
compile and hold the kernel (``tpu_custom_call``).  Nothing runs.

The topology is described inside a module fixture, so only the worker that
runs these tests loads the TPU compiler.  The engine picks its kernels from
``jax.default_backend()``, which is the CPU here; the executable tests
steer it to ``"tpu"`` for their own duration.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import esn as esn_fn
from repro.core.esn import ESNConfig
from repro.core.params import Readout
from repro.kernels import ops
from repro.kernels.diag_scan import diag_scan_pallas_raw

SLOTS, N, T, K = 64, 1024, 1024, 8
NC = N // 2                    # complex lanes of an all-pairs spectrum


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _assert_kernel(lowered):
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text


def test_scan_kernel_compiles(one_chip):
    x = _spec(one_chip, (SLOTS, T, NC))
    h = _spec(one_chip, (SLOTS, NC))
    _assert_kernel(jax.jit(
        lambda *a: diag_scan_pallas_raw(*a, interpret=False)).lower(
            x, x, x, x, h, h))


@pytest.mark.parametrize("weights", ["shared", "per_slot"])
def test_decode_kernel_compiles(one_chip, weights):
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    lead = (SLOTS,) if weights == "per_slot" else ()
    args = (s(*lead, NC), s(*lead, NC), s(SLOTS, NC), s(SLOTS, NC),
            s(SLOTS, 1), s(*lead, 1, NC), s(*lead, 1, NC), s(*lead, 1, 1),
            s(*lead, 1), s(*lead, NC, 1), s(*lead, NC, 1),
            _spec(one_chip, (SLOTS,), jnp.bool_))
    _assert_kernel(jax.jit(
        lambda *a: ops.decode_fused(*a, k=K, interpret=False)).lower(*args))


@pytest.fixture(scope="module")
def engine_args():
    """A float32 deployment-width engine and its arena; readout values do
    not matter to a compile."""
    params = esn_fn.dpg_params(ESNConfig(n=N, spectral_radius=0.95, leak=0.9,
                                         input_scaling=0.5, seed=0),
                               "noisy_golden", sigma=0.01)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    readout = Readout(jnp.zeros((params.cfg.n_features, 1), jnp.float32))
    return params, readout


@pytest.fixture
def tpu_engine(engine_args, one_chip, monkeypatch):
    from repro.serve import ReservoirEngine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params, readout = engine_args
    eng = ReservoirEngine(params, max_slots=SLOTS, readout=readout)
    abstract = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        (eng.params, eng._exec._wave_w(), eng.arena))
    return eng._exec, abstract


def test_prefill_wave_compiles(tpu_engine, one_chip):
    ex, (params, w, arena) = tpu_engine
    _assert_kernel(ex._wave_jit.lower(
        params, w, arena, _spec(one_chip, (SLOTS,), jnp.int32),
        _spec(one_chip, (SLOTS, T, 1)), _spec(one_chip, (SLOTS,), jnp.int32),
        None, method="pallas", chunk=128, want_outputs=False))


def test_closed_loop_compiles(tpu_engine, one_chip):
    ex, (params, w, arena) = tpu_engine
    _assert_kernel(ex._closed_jit.lower(
        params, w, arena, _spec(one_chip, (SLOTS,), jnp.bool_), K, None))


@pytest.mark.parametrize("executable", ["prefill_wave", "closed_loop"])
def test_sharded_executables_compile(tpu_engine, topo, executable):
    """The 4x1 arena mesh (slots data-parallel): the Pallas calls run per
    device under shard_map, so the sharded executables compile."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.sharding.rules import plan_arena
    ex, (params, w, arena) = tpu_engine
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    plan = plan_arena(mesh, ex.params, SLOTS, readout=ex.readout)
    put = lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)  # noqa: E731
    params = jax.tree.map(put, params, plan.params)
    w = put(w, plan.readout)
    arena = type(arena)(**{f: put(getattr(arena, f), plan.arena[f])
                           for f in ("states", "y_prev", "active")})
    rep = NamedSharding(mesh, P())
    rows = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=rep)
    with jax.set_mesh(mesh):
        if executable == "prefill_wave":
            lowered = ex._wave_jit.lower(
                params, w, arena, rows,
                jax.ShapeDtypeStruct((SLOTS, T, 1), jnp.float32, sharding=rep),
                rows, None, method="pallas", chunk=128, want_outputs=False)
        else:
            lowered = ex._closed_jit.lower(
                params, w, arena,
                jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=rep), K,
                None)
    _assert_kernel(lowered)
