"""Compile rehearsal for a TPU v5e: the serving and build kernels at the
full-size Gowalla x50 shapes, compiled against a *described* ``v5e:2x2``
topology (no chip needed — the TPU compiler is installed with jax).

Interpret mode accepts blocks the TPU compiler refuses (the (8, 128)
block rule, fast-memory limits, unsupported vector ops); these tests
catch that before a chip run does.  Nothing executes: a compile that
passes says nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.
"""

import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from bench.trace_reduce import MEGAKERNEL  # noqa: E402

# Gowalla x50 (get_dataset("gowalla", scale=50.0), method 2dreach):
# 3.1M vertices, 2,835,341 leaf entries -> 22,152 leaf tiles
N_VERTICES = 3_100_000
N_TILES = 22_152
P_ENTRIES = N_TILES * 128
NTP = -(-N_TILES // 128) * 128          # fine plane, TPT-rounded
B = 256                                  # serving batch bucket
KCAP = 64                                # candidate-tile capacity


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-topology compile can be written to the persistent
    # cache but never read back without a chip: keep the cache off
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _megakernel_calls(hlo: str) -> list:
    """Instruction names of the TPU custom calls that the benchmark's
    trace reduction takes for the megakernel: its device time is found
    by this name, so a refactor must not lose it."""
    calls = re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target="
                       r"\"tpu_custom_call\"", hlo)
    return [c for c in calls if MEGAKERNEL in c]


@pytest.mark.parametrize("mode", ["reach", "count", "collect"])
def test_fused_serve_compiles(one_chip, mode):
    from repro.kernels.range_query.fused import fused_serve_pallas

    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    args = (s((4, NTP), jnp.int16), s((4, NTP // 8), jnp.int32),
            s((4, P_ENTRIES), jnp.float32), s((1, P_ENTRIES), jnp.int32),
            s((4, B), jnp.int16), s((4, B), jnp.int32),
            s((4, B), jnp.float32), s((B,), jnp.int32), s((B,), jnp.int32))
    compiled = _compile(
        lambda *a: fused_serve_pallas(*a, mode=mode, kcap=KCAP,
                                      nt=N_TILES), *args)
    assert len(_megakernel_calls(compiled.as_text())) == 1
    mem = compiled.memory_analysis()
    # the arena stays in HBM: arguments dominate, the program's own
    # temporaries are the transposed query rows and repeated coarse plane
    assert mem.temp_size_in_bytes < 8 * 2 ** 20, mem


# lanes of the pointer arena at Yelp's Table-2 counts (10,791,657 leaf
# entries), of gowalla-base's (5,446,204) and of gowalla-comp's
# (2,723,102), as the benchmark builds them
YELP_TILES = 84_310
GOWALLA_BASE_TILES = 42_549
GOWALLA_COMP_TILES = 21_274


@pytest.mark.parametrize("mode", ["reach", "collect"])
@pytest.mark.parametrize("n_tiles", [GOWALLA_BASE_TILES, YELP_TILES,
                                     GOWALLA_COMP_TILES])
def test_megakernel_compiles_within_its_vmem_reckoning(one_chip, n_tiles,
                                                       mode, monkeypatch):
    """The megakernel's scoped-VMEM limit is the compiler's default at
    all three arenas' lanes, where no limit is passed.  With the default
    taken away the kernel compiles under its own reckoning at each, so
    the reckoning bounds what the compiler allocates."""
    from repro.kernels.range_query import fused as F

    ntp = -(-n_tiles // 128) * 128
    need = F.megakernel_vmem_bytes(ntp, mode=mode, kcap=KCAP)
    assert need <= F._SCOPED_VMEM_DEFAULT and F.vmem_limit(need) is None
    monkeypatch.setattr(F, "_SCOPED_VMEM_DEFAULT", 0)
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    p = n_tiles * 128
    args = (s((4, ntp), jnp.int16), s((4, ntp // 8), jnp.int32),
            s((4, p), jnp.float32), s((1, p), jnp.int32),
            s((4, B), jnp.int16), s((4, B), jnp.int32),
            s((4, B), jnp.float32), s((B,), jnp.int32), s((B,), jnp.int32))
    compiled = _compile(
        lambda *a: F.fused_serve_pallas(*a, mode=mode, kcap=KCAP,
                                        nt=n_tiles), *args)
    assert len(_megakernel_calls(compiled.as_text())) == 1


def test_prune_compiles(one_chip):
    from repro.kernels.range_query.descent import prune_tiles_pallas

    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(prune_tiles_pallas,
             s((4, NTP), jnp.float32), s((4, NTP // 8), jnp.float32),
             s((4, B), jnp.float32), s((B,), jnp.int32), s((B,), jnp.int32))


@pytest.mark.parametrize("kind", ["reach", "count", "collect", "polygon"])
def test_two_phase_scans_compile(one_chip, kind):
    from repro.kernels.range_query import analytics as A
    from repro.kernels.range_query.descent import descent_scan_pallas

    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    cand = s((B // 8, KCAP), jnp.int32)
    ent = s((4, P_ENTRIES), jnp.float32)
    rq = (s((4, B), jnp.float32), s((B,), jnp.int32), s((B,), jnp.int32))
    if kind == "reach":
        _compile(descent_scan_pallas, cand, ent, *rq)
    elif kind == "count":
        _compile(A.count_scan_pallas, cand, ent, *rq)
    elif kind == "collect":
        _compile(A.collect_scan_pallas, cand, ent,
                 s((1, P_ENTRIES), jnp.int32), *rq)
    else:
        _compile(lambda c, e, r, ln, qs, qe: A.polygon_scan_pallas(
            c, e, r, ln, qs, qe, ne=8),
            cand, ent, rq[0], s((24, B), jnp.float32), rq[1], rq[2])


def test_leaf_scan_compiles(one_chip):
    from repro.kernels.range_query.kernel import range_query_pallas

    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(range_query_pallas, s((4, 128 * 1024), jnp.float32),
             s((4, B), jnp.float32), s((B,), jnp.int32), s((B,), jnp.int32))


def test_bitset_mm_compiles(one_chip):
    from repro.kernels.bitset_mm.kernel import bitset_mm_pallas

    # one closure level of the Gowalla x10 device build: 8 padded source
    # rows x 1,108 destination words against 16,896 venue words
    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(bitset_mm_pallas, s((8, 1108), jnp.uint32),
             s((1108 * 32, 16896), jnp.uint32))


def test_seg_mbr_compiles(one_chip):
    from repro.kernels.forest_build.kernel import seg_mbr_pallas

    s = lambda shape, dt: _spec(shape, dt, one_chip)  # noqa: E731
    _compile(lambda c: seg_mbr_pallas(c, dim=2, fan=128),
             s((128 * 4, NTP), jnp.float32))


def test_sharded_fused_program_compiles_on_four_chips(topo):
    """The 4-device collective serving program (one ``shard_map`` with
    the fused megakernel per shard and a psum/pmax reduce) on a mesh of
    the described chips, the giant-tree worst case: every shard padded
    to the whole arena.  It returns one replicated vector, so that a
    batch costs one device-to-host copy."""
    from repro.cluster.sharded_engine import fused_program
    from repro.core.engine import PointerSide
    from repro.kernels.range_query.fused import QuantGrid
    from repro.kernels.range_query.kernel import TB

    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    shard = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    n_trees = 4096
    side = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(
            PointerSide.tree_unflatten(("base", 2), [0, 0, 0] + [None] * 4)),
        [_spec((N_VERTICES, 2), jnp.float32, rep),
         _spec((N_VERTICES,), jnp.bool_, rep),
         _spec((N_VERTICES,), jnp.int32, rep)])
    grid = QuantGrid(*(_spec((2,), jnp.float32, rep) for _ in range(3)))
    prog = fused_program(mesh, shards_per_dev=1, nt=N_TILES, dim=2,
                         kcap=KCAP, impl="pallas", interpret=False)
    compiled = prog.lower(
        side, grid,
        _spec((4, 4, NTP), jnp.int16, shard),
        _spec((4, 4, NTP // 8), jnp.int32, shard),
        _spec((4, 4, P_ENTRIES), jnp.float32, shard),
        *(_spec((n_trees,), jnp.int32, rep) for _ in range(3)),
        _spec((B,), jnp.int32, rep), _spec((4, B), jnp.float32, rep),
    ).compile()
    (out,) = jax.tree.leaves(compiled.out_info)
    (out_sharding,) = jax.tree.leaves(compiled.output_shardings)
    # mx, 4 shards' candidate counts per query tile, answer and owner
    # per lane
    assert out.dtype == jnp.int32 and out.shape == (1 + 4 * (B // TB) + 2 * B,)
    assert out_sharding.is_fully_replicated
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    assert "all-reduce" in hlo
    assert len(_megakernel_calls(hlo)) == 1


def test_engine_programs_take_the_index_as_arguments():
    """The engine's programs read the index from arguments: a
    closed-over array would be embedded as a constant in every compiled
    program (on the chip: one index copy per bucket and mode)."""
    from repro.core import QueryEngine, build_index
    from repro.data import get_dataset, workload

    g = get_dataset("gowalla", scale=1.0)
    eng = QueryEngine(build_index(g, "2dreach"))
    us, rects = workload(g, 64, seed=1)
    eng.query_batch(us, rects)
    _, us_dev, rsoa_dev = eng._padder.pad(us, rects)
    low = eng._fused._jitted.lower(eng._dev, us_dev, rsoa_dev,
                                   mode="reach", kcap=eng._kb_hwm)
    assert len(jax.tree.leaves(low.args_info)) == \
        len(jax.tree.leaves(eng._dev)) + 2
    index_bytes = sum(x.nbytes for x in jax.tree.leaves(eng._dev))
    assert len(low.as_text()) < index_bytes / 20
