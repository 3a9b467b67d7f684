"""Compile rehearsal for a TPU v5e chip that is described, not attached.

The PPoT dispatch kernels have to pass Mosaic's checks at every batch the
engine is called with, and also when the serving scan traces them under
``jax.enable_x64``. Interpret mode checks neither, so these tests compile
ahead of time for one chip of a ``v5e:2x2`` topology and look for the
kernel (``tpu_custom_call``) in the compiled program: the kernels alone,
the engine's TPU branch, and one chunk of the served scan. Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import itertools
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import dispatch as dsp
from repro.core import policies as pol
from repro.kernels.ppot_dispatch import kernel as K
from repro.serving import scanloop

SHAPES = [(n, B) for n in (64, 1024) for B in (128, 4096)]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler to describe the chip with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(sharding, name, n, B):
    f32, i32 = jnp.float32, jnp.int32
    w = [_spec(sharding, (n,), f32), _spec(sharding, (n,), i32)]
    if name == "fused_alias":  # prob, alias, q
        w.append(_spec(sharding, (n,), i32))
    n_u = 4 if name == "fused_alias" else 2
    return w + [_spec(sharding, (B,), f32)] * n_u


KERNELS = {
    "v1": K.ppot_dispatch,
    "fused": K.ppot_dispatch_fused,
    "fused_alias": K.ppot_dispatch_fused_alias,
}


@pytest.mark.parametrize("n,B", SHAPES)
@pytest.mark.parametrize("name", sorted(KERNELS))
def test_dispatch_kernel_compiles_for_v5e(one_chip, name, n, B):
    compiled = KERNELS[name].lower(
        *_kernel_args(one_chip, name, n, B)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n,B", [(64, 128), (1024, 4096)])
def test_engine_kernel_compiles_under_x64(one_chip, monkeypatch, n, B):
    """The serving scan's dispatch call: the engine on its TPU branch
    (fused alias kernel, no mask) traced inside ``jax.enable_x64``, where
    an index or reduction left to default types would be 64-bit."""
    monkeypatch.setattr(dsp, "_on_tpu", lambda: True)

    def engine(key, q, mu, prob, alias):
        return dsp._dispatch_impl(
            pol.PPOT_SQ2, key, q, mu, mu, pol.default_policy_config(), B,
            table=dsp.AliasTable(prob=prob, alias=alias),
        )

    f32, i32 = jnp.float32, jnp.int32
    with jax.enable_x64(True):
        compiled = jax.jit(engine).lower(
            _spec(one_chip, (2,), jnp.uint32), _spec(one_chip, (n,), i32),
            _spec(one_chip, (n,), f32), _spec(one_chip, (n,), f32),
            _spec(one_chip, (n,), i32),
        ).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pool_chain_compiles_under_x64(one_chip):
    """The replica-pool chain at the benchmark cell's shape (n=60 workers,
    m=136 submissions a turn): a while loop with a dynamic trip count over
    float64 rows of the rank grid, gathered and scattered by int32."""
    n, m = 60, 136
    f64 = jnp.float64
    with jax.enable_x64(True):
        compiled = jax.jit(scanloop.pool_chain).lower(
            _spec(one_chip, (n,), f64), _spec(one_chip, (m,), jnp.int32),
            _spec(one_chip, (m,), f64), _spec(one_chip, (m,), f64),
            _spec(one_chip, (m,), bool), _spec(one_chip, (n,), f64),
        ).compile()
    assert re.search(r"\bwhile\(", compiled.as_text())


def test_served_scan_chunk_compiles(one_chip, monkeypatch):
    """One 512-turn chunk of the load harness's served scan (n=64, k=128,
    pend_cap 8192, stream-only telemetry) on its TPU branch: the f64 event
    clock, the flush sorts and the fused alias kernel in one program."""
    from benchmarks import loadtest as lt
    from repro import obs
    from repro.load import ScenarioStream, run_stream_scan
    from repro.serving import router as rt

    class Lowered(Exception):
        pass

    texts = []
    # _build_scan without its cache: a fresh jit, never traced on the CPU
    uncached = scanloop._build_scan.__wrapped__

    def build(*args):
        run = uncached(*args)

        def compile_only(lcfg, carry, xs):
            def spec(x):
                return _spec(one_chip, np.shape(x), jnp.result_type(x))

            texts.append(run.lower(*jax.tree.map(
                spec, (lcfg, carry, xs))).compile().as_text())
            raise Lowered

        return compile_only

    monkeypatch.setattr(dsp, "_on_tpu", lambda: True)
    monkeypatch.setattr(scanloop, "_build_scan", build)
    scn = lt.make_scenario(lt.HORIZON_SMOKE)
    stream = ScenarioStream(scn, seed=0, arrival_batch=lt.ARRIVAL_BATCH)
    with pytest.raises(Lowered):
        run_stream_scan(
            lt.make_router(0), rt.SimulatedPool(lt._speeds()),
            itertools.islice(stream.chunks(lt.CHUNK_TURNS), 1),
            fake_cost=scn.request_cost * 0.25, pend_cap=lt.PEND_CAP,
            comp_cap=lt.COMP_CAP,
            observe=obs.ObserveConfig(window_turns=lt.WINDOW_TURNS,
                                      emit_responses=False),
        )
    assert "tpu_custom_call" in texts[0]
    # every stage scope survives into the compiled program's op_name
    # metadata, which the device trace's HLO protos carry
    from repro.obs import tracing as obt

    assert set(re.findall(r"rosella\.[a-z_]+", texts[0])) == {
        obt.PREFIX + s for s in obt.STAGES}


def test_crash_cell_scan_compiles(one_chip, monkeypatch):
    """One 512-turn call of the crash cell's faulty scan (n=60, k=128,
    pend_cap 8192, 240 probe-burst slots, the recovery layer on, responses
    emitted) on its TPU branch: the masked dispatch, the retry and backup
    lexsorts and the recovery scopes in one program."""
    from bench import run as br
    from repro.obs import tracing as obt

    class Lowered(Exception):
        pass

    texts = []
    uncached = scanloop._build_scan_faulty.__wrapped__

    def build(*args):
        run = uncached(*args)

        def compile_only(lcfg, carry, xs):
            def spec(x):
                return _spec(one_chip, np.shape(x), jnp.result_type(x))

            texts.append(run.lower(*jax.tree.map(
                spec, (lcfg, carry, xs))).compile().as_text())
            raise Lowered

        return compile_only

    monkeypatch.setattr(dsp, "_on_tpu", lambda: True)
    monkeypatch.setattr(scanloop, "_build_scan_faulty", build)
    cell = br.Cell("s2x4-crash-bulk")
    with pytest.raises(Lowered):
        cell.driver.Driver(cell, 2**31 + 11).setup()
    scopes = set(re.findall(r"rosella\.[a-z_]+", texts[0]))
    assert scopes == {obt.PREFIX + s
                      for s in obt.STAGES + obt.RECOVERY_STAGES}
    assert re.search(r"\bsort\(", texts[0])
