"""Ahead-of-time compiles for a TPU v5e, made without a chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described rather than attached (a ``v5e:2x2`` topology), so what the
chip's compiler would refuse — a block that breaks the (8, 128) tiling,
a kernel that outgrows VMEM, an integer matmul, a program that does not
fit the chip's 16 GB — fails here, at no chip time.  Nothing runs: these
tests say nothing about results or speed.

The deployment size is the Enzian home directory that ``chip_smoke.py``
runs: R=48 caching agents, L=131,072 lines of 32 float32.  The topology
is described inside a module fixture (never at import time), so every
test worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import coherency_step as coh
from repro.traffic import (LAT_EDGES, EngineConfig, StreamConfig,
                           WorkloadSpec, stream_program)

R, L, BLOCK = 48, 131_072, 32
#: v5e HBM per chip (Google Cloud documentation, "TPU v5e").
V5E_HBM_BYTES = 16 * 10 ** 9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with JAX's persistent compilation cache
    off: an entry compiled for a described chip cannot be read back."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


W = -(-R // 32)
EDGES = tuple(int(e) for e in LAT_EDGES)
KERNELS = {
    "credit_rank": (lambda a, c: coh.credit_rank(a, c, interpret=False),
                    [((R, L), bool), ((R, L), bool)]),
    "arb_winner_h1": (lambda r, p: coh.arb_winner(r, p, interpret=False),
                      [((R + 1, L), bool), ((L,), jnp.int32)]),
    "arb_winner_h2": (lambda r, p: coh.arb_winner(r, p, interpret=False),
                      [((2, R + 1, L // 2), bool),
                       ((2, L // 2), jnp.int32)]),
    "count_fold": (lambda m, g, p: coh.count_fold(m, g, p,
                                                   interpret=False),
                   [((R, L), bool), ((R, L), jnp.int8), ((R, L), bool)]),
    "lat_hist": (lambda t, r: coh.lat_hist(t, r, EDGES, interpret=False),
                 [((R, L), jnp.int32), ((R, L), bool)]),
    "packed_any": (lambda w: coh.packed_any(w, interpret=False),
                   [((2, L, W), jnp.uint32)]),
    "packed_fanout": (lambda p, e, n, s, x: coh.packed_fanout(
        p, e, n, s, x, interpret=False),
        [((L, W), jnp.uint32), ((L, W), jnp.uint32), ((L,), jnp.int32),
         ((L,), bool), ((L,), bool)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_coherency_kernel_compiles_for_v5e(name, one_chip):
    """Each coherency-step kernel lowers through Mosaic at deployment
    size (``arb_winner`` also at H=2 homes) and is a real custom call."""
    fn, shapes = KERNELS[name]
    compiled = _compile(fn, shapes, one_chip)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("lines", [4096, L])
def test_stream_program_compiles_and_fits_v5e(lines, one_chip):
    """The default XLA streaming program — the one ``run_stream`` runs —
    compiles for one v5e, and its own memory analysis fits the chip."""
    eng = EngineConfig(remotes=R, lines=lines, block=BLOCK).build()
    prog, operands = stream_program(eng, StreamConfig(
        WorkloadSpec("zipfian", ops=16, seed=0), collect_trace=True))
    placed = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        operands)
    ma = prog.lower(*placed).compile().memory_analysis()
    need = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
    # the engine state is donated: every output but the counters aliases
    # an argument buffer.
    assert ma.alias_size_in_bytes > 0.9 * ma.argument_size_in_bytes
    assert need < V5E_HBM_BYTES, f"{need} bytes do not fit a v5e"
