"""Ahead-of-time compiles of the serving path for a TPU v5e that is
described, not attached: the Pallas kernels at each benchmark
configuration's widths and the engine's jitted prefill-wave and closed-loop
executables.  Each must compile and hold the kernel (``tpu_custom_call``).
Nothing runs.

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

SLOTS, K = 64, 8
#: (N, D, prefill rows, prefill steps) of each benchmark configuration:
#: mso-n1024 at its widest wave, ks22-n5000 (D=64) at its cell's widest
#: prefill wave, 8 rows of 2048 steps.
WIDTHS = {"mso-n1024": (1024, 1, SLOTS, 1024),
          "ks22-n5000": (5000, 64, 8, 2048)}


def _lanes(n):
    """Complex lanes of an all-pairs spectrum, padded to whole 128-lane
    tiles as the kernels' callers pad them."""
    return -(-(n // 2) // 128) * 128


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


@pytest.mark.parametrize("widths", WIDTHS)
def test_scan_kernel_compiles(one_chip, widths):
    n, _, _, t = WIDTHS[widths]
    x = _spec(one_chip, (SLOTS, t, _lanes(n)))
    h = _spec(one_chip, (SLOTS, _lanes(n)))
    _assert_kernel(jax.jit(
        lambda *a: diag_scan_pallas_raw(*a, interpret=False)).lower(
            x, x, x, x, h, h))


# Per-slot weights (a param batch or readout pool) compile at D=1 only: at
# D=64 a slot tile's (8, 128, 2560) weight blocks need 60 MB of VMEM, past
# the 16 MB scoped limit, and no configuration serves them there.
@pytest.mark.parametrize("weights,widths", [
    ("shared", "mso-n1024"), ("per_slot", "mso-n1024"),
    ("shared", "ks22-n5000")])
def test_decode_kernel_compiles(one_chip, weights, widths):
    n, d, _, _ = WIDTHS[widths]
    nc = n // 2
    s = lambda *shape: _spec(one_chip, shape)  # noqa: E731
    lead = (SLOTS,) if weights == "per_slot" else ()
    args = (s(*lead, nc), s(*lead, nc), s(SLOTS, nc), s(SLOTS, nc),
            s(SLOTS, d), s(*lead, d, nc), s(*lead, d, nc), s(*lead, d, d),
            s(*lead, d), s(*lead, nc, d), s(*lead, nc, d),
            _spec(one_chip, (SLOTS,), jnp.bool_))
    _assert_kernel(jax.jit(
        lambda *a: ops.decode_fused(*a, k=K, interpret=False)).lower(*args))


@pytest.fixture(scope="module", params=list(WIDTHS))
def engine_args(request):
    """A float32 engine at a configuration's widths, its arena and its
    widest prefill wave; readout values do not matter to a compile."""
    n, d, rows, t = WIDTHS[request.param]
    params = esn_fn.dpg_params(ESNConfig(n=n, d_in=d, d_out=d,
                                         spectral_radius=0.95, leak=0.9,
                                         input_scaling=0.5, seed=0),
                               "noisy_golden", sigma=0.01)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    readout = Readout(jnp.zeros((params.cfg.n_features, d), jnp.float32))
    return params, readout, (rows, t, d)


@pytest.fixture
def tpu_engine(engine_args, one_chip, monkeypatch):
    from repro.serve import ReservoirEngine
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    params, readout, wave = engine_args
    eng = ReservoirEngine(params, max_slots=SLOTS, readout=readout)
    abstract = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        (eng.params, eng._exec._wave_w(), eng.arena))
    return eng._exec, abstract, wave


def test_prefill_wave_compiles(tpu_engine, one_chip):
    ex, (params, w, arena), (rows, t, d) = tpu_engine
    _assert_kernel(ex._wave_jit.lower(
        params, w, arena, _spec(one_chip, (rows,), jnp.int32),
        _spec(one_chip, (rows, t, d)), _spec(one_chip, (rows,), jnp.int32),
        None, method="pallas", chunk=128, want_outputs=False))


def test_closed_loop_compiles(tpu_engine, one_chip):
    ex, (params, w, arena), _ = tpu_engine
    _assert_kernel(ex._closed_jit.lower(
        params, w, arena, _spec(one_chip, (SLOTS,), jnp.bool_), K, None))


@pytest.mark.parametrize("executable", ["prefill_wave", "closed_loop"])
def test_sharded_executables_compile(tpu_engine, topo, executable):
    """The 4x1 arena mesh (slots data-parallel): the Pallas calls run per
    device under shard_map, so the sharded executables compile."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.sharding.rules import plan_arena
    ex, (params, w, arena), (_, t, d) = tpu_engine
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
                jax.ShapeDtypeStruct((SLOTS, t, d), jnp.float32, sharding=rep),
                rows, None, method="pallas", chunk=128, want_outputs=False)
        else:
            lowered = ex._closed_jit.lower(
                params, w, arena,
                jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=rep), K,
                None)
    _assert_kernel(lowered)
