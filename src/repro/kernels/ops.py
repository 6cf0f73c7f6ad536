"""Public jit'd wrappers over the Pallas kernels.

Each op selects `interpret` mode from the platform (:func:`platform`):
compiled kernels on a TPU (v5e is the target), Python-interpreted bodies
on the CPU (the tests), and an error on any other backend.  Model code calls these;
pure-JAX fallbacks (`*_jnp`) are what the multi-pod dry-run lowers, since
Pallas TPU kernels cannot lower on the CPU host platform.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.cache_sim import cache_sim as _cache_sim_kernel
from repro.kernels.cache_sim import mesi_cache_sim as _mesi_kernel
from repro.kernels.cache_sim import mesi_dyn_segment as _mesi_dyn_segment
from repro.kernels.cache_sim import mesi_segment as _mesi_segment
from repro.kernels.flash_attention import flash_attention as _flash_kernel
from repro.kernels.paged_attention import paged_attention as _paged_kernel
from repro.kernels.stream_triad import stream_triad as _triad_kernel

Array = jax.Array


def platform() -> str:
    """The platform jitted calls run on: the default device's when one is
    set (``jax.default_device``), else the default backend."""
    dev = jax.config.jax_default_device
    if dev is None:
        return jax.default_backend()
    return dev if isinstance(dev, str) else dev.platform


def _interpret() -> bool:
    """Interpret on the CPU, compile on a TPU; refuse any other backend."""
    backend = platform()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run compiled on a TPU or interpreted on the "
            f"CPU; the default backend is {backend!r}")
    return backend == "cpu"


def cache_sim(addr: Array, *, n_sets: int, n_ways: int, chunk: int = 512):
    # sentinel padding to a chunk multiple happens inside the kernel wrapper
    return _cache_sim_kernel(addr.astype(jnp.int32), n_sets=n_sets,
                             n_ways=n_ways, chunk=chunk,
                             interpret=_interpret())


def mesi_cache_sim(addr: Array, is_write: Array, core: Array, tier: Array,
                   *, params, chunk: int = 512):
    """Batched two-level MESI + tier simulation (engine `pallas` backend)."""
    return _mesi_kernel(addr, is_write, core, tier, params=params,
                        chunk=chunk, interpret=_interpret())


def mesi_run_segment(carry, addr: Array, is_write: Array, core: Array,
                     tier: Array, *, params, chunk: int = 512):
    """Advance the engine's packed batch carry over one trace segment.

    The kernel-side twin of :func:`repro.core.engine.run_batch_segment`:
    same ``(l1p, l2p, stats, t)`` carry in and out (checkpoint/resume
    replays it), bitwise-equal stats and state.
    """
    return _mesi_segment(carry, addr, is_write, core, tier, params=params,
                         chunk=chunk, interpret=_interpret())


def mesi_dyn_segment(carry, addr: Array, is_write: Array, core: Array,
                     tier: Array, dyn_flag, n_pages, budget, threshold,
                     period, dram_cap, ssd_tid, cxl_cap,
                     page_target_lines, s_warm, s_meas,
                     s_per, *, params, k_max: int, count_bound: int,
                     chunk: int = 1024):
    """Advance the batched epoch carry over a (B, E, slot_len) segment.

    The kernel-side twin of :func:`repro.core.tiering_dyn.
    run_dynamic_segment`: same 9-tuple carry and per-slot outputs
    (slots/snapshots/meas), bitwise-equal across dynamic tiering,
    three-tier SSD, sampling and static ride-along rows.  ``chunk`` is
    the trace entries of one SMEM block, at most a slot.
    """
    return _mesi_dyn_segment(carry, addr, is_write, core, tier, dyn_flag,
                             n_pages, budget, threshold, period, dram_cap,
                             ssd_tid, cxl_cap,
                             page_target_lines, s_warm, s_meas, s_per,
                             params=params, k_max=k_max,
                             count_bound=count_bound, chunk=chunk,
                             interpret=_interpret())


def stream_triad(b: Array, c: Array, s) -> Array:
    return _triad_kernel(b, c, s, interpret=_interpret())


def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    window: Optional[int] = None) -> Array:
    return _flash_kernel(q, k, v, causal=causal, window=window,
                         interpret=_interpret())


def paged_attention(q: Array, k_pages: Array, v_pages: Array,
                    block_table: Array, context_lens: Array) -> Array:
    return _paged_kernel(q, k_pages, v_pages, block_table, context_lens,
                         interpret=_interpret())


# Pure-jnp fallbacks (what pjit lowers in the dry-run / on CPU hosts).
cache_sim_jnp = ref.cache_sim
stream_triad_jnp = ref.stream_triad
flash_attention_jnp = ref.flash_attention
paged_attention_jnp = ref.paged_attention
