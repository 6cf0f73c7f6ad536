"""The main path's device programs compile for a TPU v5e that is only
described, not attached.

The TPU compiler ships with jaxlib, so these tests run on any host: they
catch a program the chip's compiler would refuse (a tiling rule, a
memory limit) before any chip time is spent.  Nothing runs, so they say
nothing about results or speed.

Geometry is the paper's Table-I host (4 cores, 64 KiB 8-way L1, 2 MiB
16-way L2) with five route targets (``switched(4)``); batch and segment
are cut so each program compiles in seconds.  The static program's
Pallas MESI kernels (``mesi_cache_sim``, ``mesi_segment``) and the
epoch program's (``mesi_dyn_segment``), what a TPU runs by default,
compile with ``interpret=False``: the static ones at two and five
targets and two batch widths, and at one AMD EPYC 9004 CCD (8 cores, a
32 MiB L3), the epoch one at five targets and two batch widths.  The
VMEM rule that keeps a larger cache on the reference scan matches what
the compiler accepts, for both programs.  One
program too big for the chip's HBM checks that the compiler's refusal
reads as an out-of-memory to the resilient executor, which then narrows
the segment instead of giving up.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import cache as cache_mod
from repro.core import engine, resilience, tiering_dyn
from repro.kernels import cache_sim

PARAMS = cache_mod.CacheParams(cores=4, n_targets=5)
BATCH = 8
SLOT = 4096                       # DynamicTiering().epoch_len
# STREAM triad's pages at 2 x L2: at 8 x L2 (4,098 pages) the dynamic
# program takes ~12 s to compile
PAGES = 2 * 2 * 1024 * 1024 // 4096 + 2


@pytest.fixture(scope="module")
def one_chip():
    # libtpu would otherwise write its logs under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:     # no TPU compiler in this jaxlib
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A described chip's programs can be written to the persistent cache
    but never read back; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kind(sharding) -> str:
    (device,) = sharding.device_set
    return device.device_kind


def _shapes(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, jnp.int32,
                                       sharding=sharding), tree)


def test_static_segment_compiles(one_chip, no_compile_cache):
    carry = _shapes(jax.eval_shape(
        functools.partial(engine.init_batch_carry, PARAMS, BATCH)), one_chip)
    trace = [jax.ShapeDtypeStruct((BATCH, 2 * SLOT), jnp.int32,
                                  sharding=one_chip)] * 4
    compiled = engine._segment_stepper(True).lower(
        PARAMS, carry, *trace).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


@pytest.mark.parametrize("batch", [2, 8])
@pytest.mark.parametrize("n_targets", [2, 5])
@pytest.mark.parametrize("kernel", ["mesi_cache_sim", "mesi_segment"])
def test_static_kernel_compiles(one_chip, no_compile_cache, kernel,
                                n_targets, batch):
    """The static program's kernels lower through Mosaic and compile."""
    p = cache_mod.CacheParams(cores=4, n_targets=n_targets)
    trace = [jax.ShapeDtypeStruct((batch, 2 * SLOT), jnp.int32,
                                  sharding=one_chip)] * 4
    carry = ([_shapes(jax.eval_shape(functools.partial(
        engine.init_batch_carry, p, batch)), one_chip)]
        if kernel == "mesi_segment" else [])
    compiled = getattr(cache_sim, kernel).lower(
        *carry, *trace, params=p, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("kernel", ["mesi_cache_sim", "mesi_segment"])
def test_static_kernel_compiles_at_a_genoa_ccd(one_chip, no_compile_cache,
                                               kernel, batch):
    """One AMD EPYC 9004 CCD: 8 cores, 32 KiB 8-way L1s, a 32 MiB 16-way
    L3 as the shared level.  Its 10.05 MiB of state blocks pass Mosaic's
    default 16 MiB of scoped VMEM once double-buffered; the kernels ask
    for what they hold instead."""
    p = cache_mod.CacheParams(cores=8, n_targets=2, l1_bytes=32 * 1024,
                              l2_bytes=32 * 2 ** 20)
    assert cache_sim.fits_chip(p, _kind(one_chip))
    trace = [jax.ShapeDtypeStruct((batch, 2 * SLOT), jnp.int32,
                                  sharding=one_chip)] * 4
    carry = ([_shapes(jax.eval_shape(functools.partial(
        engine.init_batch_carry, p, batch)), one_chip)]
        if kernel == "mesi_segment" else [])
    compiled = getattr(cache_sim, kernel).lower(
        *carry, *trace, params=p, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kernel", ["mesi_cache_sim", "mesi_segment"])
@pytest.mark.parametrize("l2_mib", [256, 512])
def test_vmem_rule_matches_the_compiler(one_chip, no_compile_cache, l2_mib,
                                        kernel):
    """The default backend runs the kernel only where one row's state
    blocks fit the chip's VMEM (``engine.resolve_backend``).  At a batch
    where XLA cannot hold whole operands in VMEM itself, the largest
    16-way L2 the rule admits compiles and the next size up runs out of
    VMEM, so the rule is neither too strict nor too lax."""
    p = cache_mod.CacheParams(cores=4, n_targets=2,
                              l2_bytes=l2_mib * 2 ** 20)
    fits = cache_sim.fits_chip(p, _kind(one_chip))
    assert fits == (l2_mib == 256)
    batch = 8
    trace = [jax.ShapeDtypeStruct((batch, cache_sim.TRACE_TILE), jnp.int32,
                                  sharding=one_chip)] * 4
    carry = ([_shapes(jax.eval_shape(functools.partial(
        engine.init_batch_carry, p, batch)), one_chip)]
        if kernel == "mesi_segment" else [])
    lowered = getattr(cache_sim, kernel).lower(*carry, *trace, params=p,
                                               interpret=False)
    if fits:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="memory space vmem"):
            lowered.compile()


def _dyn_args(p, batch, slots, slot, pages, sharding):
    """Shapes of an epoch segment's carry, trace and per-row scalars."""
    page_map0 = jax.ShapeDtypeStruct((batch, pages), jnp.int32)
    carry = _shapes(jax.eval_shape(
        functools.partial(tiering_dyn.init_dyn_carry, p), page_map0),
        sharding)

    def arr(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=sharding)

    trace = [arr(batch, slots, slot)] * 4
    scalars = ([arr(batch)] * 8 + [arr(batch, pages, p.n_targets)]
               + [arr(batch)] * 3)
    return [carry, *trace, *scalars]


@pytest.mark.parametrize("batch", [1, 8])
def test_epoch_kernel_compiles(one_chip, no_compile_cache, batch):
    """The epoch program's kernel lowers through Mosaic and compiles:
    two slots of 4,096 accesses, four SMEM trace blocks each."""
    compiled = cache_sim.mesi_dyn_segment.lower(
        *_dyn_args(PARAMS, batch, 2, SLOT, PAGES, one_chip), params=PARAMS,
        k_max=8, count_bound=SLOT + 1, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("l2_mib", [256, 512])
def test_epoch_vmem_rule_matches_the_compiler(one_chip, no_compile_cache,
                                              l2_mib):
    """As for the static kernels: the largest 16-way L2 the rule admits
    for the epoch kernel, page planes included, compiles, and the next
    size up runs out of VMEM."""
    p = cache_mod.CacheParams(cores=4, n_targets=2,
                              l2_bytes=l2_mib * 2 ** 20)
    fits = cache_sim.fits_chip(p, _kind(one_chip), n_pages=PAGES)
    assert fits == (l2_mib == 256)
    lowered = cache_sim.mesi_dyn_segment.lower(
        *_dyn_args(p, 8, 1, cache_sim.TRACE_TILE, PAGES, one_chip),
        params=p, k_max=8, count_bound=cache_sim.TRACE_TILE + 1,
        interpret=False)
    if fits:
        assert "tpu_custom_call" in lowered.compile().as_text()
    else:
        with pytest.raises(jax.errors.JaxRuntimeError,
                           match="memory space vmem"):
            lowered.compile()


def test_dynamic_segment_compiles(one_chip, no_compile_cache):
    compiled = tiering_dyn._dyn_segment_stepper(True).lower(
        PARAMS, 8, SLOT + 1,
        *_dyn_args(PARAMS, BATCH, 1, SLOT, PAGES, one_chip)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0


def test_program_too_big_for_hbm_reads_as_oom(one_chip, no_compile_cache):
    """XLA:TPU refuses a program whose buffers exceed HBM while it
    compiles; that refusal must degrade (a narrower segment may fit),
    not be fatal like other compile errors."""
    x = jax.ShapeDtypeStruct((2 ** 15, 2 ** 16), jnp.float32,
                             sharding=one_chip)            # 8 GiB

    def reversed_copies(x):
        return (x * 2.0)[::-1] + (x * 3.0)[:, ::-1] + (x * 5.0)[::-1, ::-1]

    with pytest.raises(jax.errors.JaxRuntimeError) as err:
        jax.jit(reversed_copies).lower(x).compile()
    assert "memory space hbm" in str(err.value)
    assert resilience.classify_failure(err.value) == "oom"
