"""Pallas TPU kernel: paged-KV decode attention (tiered KV cache hot-spot).

The paper's flagship LLM use-case is spilling KV-cache into CXL memory.
Our serving path stores KV in fixed-size **pages** indexed by a per-sequence
block table (tier-agnostic: a page's physical residency — HBM or CXL pool —
is the tiering layer's business, see :mod:`repro.memory.kvcache`).  Decode
attention then has to gather pages by table lookup: this kernel fuses the
gather with online-softmax attention so gathered K/V tiles never round-trip
through HBM.

TPU-native design: grid = (batch,); the page pool stays in ANY/HBM memory
space and each page is pulled by dynamic ref indexing (async-copy on real
TPUs, emulated in interpret mode); per-sequence (m, l, acc) statistics live
in VMEM scratch; the per-page masked online-softmax update is identical to
flash attention's.  GQA: H query heads share K kv heads (H % K == 0).

Validated against :func:`repro.kernels.ref.paged_attention`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array
NEG_INF = -1e30


def _paged_kernel(q_ref, bt_ref, len_ref, kp_ref, vp_ref, o_ref,
                  m_s, l_s, acc_s, *, page: int, nblk: int, kh: int,
                  groups: int, d: int, scale: float):
    h = kh * groups
    q = q_ref[0].astype(jnp.float32) * scale            # (h, d)
    ctx = len_ref[0]
    m_s[...] = jnp.full_like(m_s, NEG_INF)
    l_s[...] = jnp.zeros_like(l_s)
    acc_s[...] = jnp.zeros_like(acc_s)

    n_live = (ctx + page - 1) // page

    def blk_step(j, _):
        def compute():
            pid = bt_ref[0, j]
            k = kp_ref[pid]                             # (page, kh, d)
            v = vp_ref[pid]
            kf = k.astype(jnp.float32)
            vf = v.astype(jnp.float32)
            # logits: (h, page) via grouped heads
            qg = q.reshape(kh, groups, d)
            s = jnp.einsum("kgd,pkd->kgp", qg, kf).reshape(h, page)
            pos = j * page + jax.lax.broadcasted_iota(jnp.int32, (h, page), 1)
            s = jnp.where(pos < ctx, s, NEG_INF)
            m_prev, l_prev = m_s[:, 0], l_s[:, 0]
            m_cur = jnp.maximum(m_prev, s.max(axis=-1))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[:, None])             # (h, page)
            l_cur = l_prev * alpha + p.sum(axis=-1)
            pg = p.reshape(kh, groups, page)
            upd = jnp.einsum("kgp,pkd->kgd", pg, vf).reshape(h, d)
            acc_s[...] = acc_s[...] * alpha[:, None] + upd
            m_s[:, 0] = m_cur
            l_s[:, 0] = l_cur
        pl.when(j < n_live)(compute)
        return 0

    jax.lax.fori_loop(0, nblk, blk_step, 0)
    l = l_s[:, 0]
    safe_l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc_s[...] / safe_l[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q: Array, k_pages: Array, v_pages: Array,
                    block_table: Array, context_lens: Array,
                    *, interpret: bool = True) -> Array:
    """Decode attention over a paged KV pool.

    Shapes: q (B,H,D); k_pages/v_pages (P, page, K, D);
    block_table (B, nblk) int32; context_lens (B,) int32 -> out (B,H,D).
    """
    b, h, d = q.shape
    p_, page, kh, _ = k_pages.shape
    nblk = block_table.shape[1]
    if h % kh != 0:
        raise ValueError(f"q heads {h} must be a multiple of kv heads {kh}")
    groups = h // kh
    scale = d ** -0.5
    kern = functools.partial(_paged_kernel, page=page, nblk=nblk, kh=kh,
                             groups=groups, d=d, scale=scale)
    return pl.pallas_call(
        kern,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, nblk), lambda i: (i, 0)),
            pl.BlockSpec((1,), lambda i: (i,)),
            pl.BlockSpec(memory_space=pl.ANY),   # page pool stays off-VMEM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h, d), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, block_table.astype(jnp.int32), context_lens.astype(jnp.int32),
      k_pages, v_pages)
