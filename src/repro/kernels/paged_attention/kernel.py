"""Pallas TPU kernel: GQA decode attention over the two-level paged KV cache.

This is the serving hot path the paper's technique feeds: KV pages live in the
GPAC-managed tiered store, and decode gathers them *through the block table*.
The physical page id is scalar-prefetched into the K/V index maps, so the page
walk (the paper's EPT analogue) costs one SMEM read per grid step while the
page payload streams HBM->VMEM double-buffered.

Layouts (chosen so the page dimension is contiguous for one-DMA-per-page):
    q:        (B, KVH, G, hd)    G = n_q_heads // n_kv_heads
    k_pages:  (KVH, n_pages, page_size, hd)
    v_pages:  (KVH, n_pages, page_size, hd)
    btab:     int32 (B, pages_per_seq)   physical page per sequence slot
    lens:     int32 (B,)                 current KV length per sequence

Grid: (B, KVH, pages_per_seq); the page axis is sequential ("arbitrary") and
accumulates online softmax in VMEM scratch. Fully padded pages (slot beyond
ceil(len/page_size)) are masked; their btab entries are clamped to 0 by the
wrapper so the index map stays in range.

On real TPU, ``hd`` is 64-256 (lane-aligned) and ``G`` lands in sublanes; the
scratch carries (G, 1) running max / denominator per kv-head group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(
    lens_ref,  # SMEM int32 (B,)
    btab_ref,  # SMEM int32 (B, pages_per_seq)
    q_ref,  # (1, 1, G, hd)
    k_ref,  # (1, 1, page_size, hd)
    v_ref,  # (1, 1, page_size, hd)
    o_ref,  # (1, 1, G, hd)
    m_ref,  # scratch (G, 1) f32
    l_ref,  # scratch (G, 1) f32
    acc_ref,  # scratch (G, hd) f32
    *,
    page_size: int,
    pages_per_seq: int,
    scale: float,
):
    b = pl.program_id(0)
    p = pl.program_id(2)

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    seq_len = lens_ref[b]
    pos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
    in_seq = pos < seq_len  # (1, page_size)

    q = q_ref[0, 0].astype(jnp.float32)  # (G, hd)
    k = k_ref[0, 0].astype(jnp.float32)  # (page_size, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (G, page_size)
    s = jnp.where(in_seq, s, NEG_INF)

    m_prev = m_ref[...]  # (G, 1)
    m_cur = s.max(axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    # exp of fully-masked lanes underflows to 0 (NEG_INF - m_new <= 0)
    pexp = jnp.exp(s - m_new)
    pexp = jnp.where(in_seq, pexp, 0.0)
    l_new = l_ref[...] * alpha + pexp.sum(axis=1, keepdims=True)
    acc_new = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new
    l_ref[...] = l_new
    acc_ref[...] = acc_new

    @pl.when(p == pages_per_seq - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,  # (B, KVH, G, hd)
    k_pages: jax.Array,  # (KVH, n_pages, page_size, hd)
    v_pages: jax.Array,
    btab: jax.Array,  # int32 (B, pages_per_seq), pre-clamped to [0, n_pages)
    lens: jax.Array,  # int32 (B,)
    *,
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """Returns (B, KVH, G, hd) attention output."""
    B, KVH, G, hd = q.shape
    _, n_pages, page_size, _ = k_pages.shape
    pages_per_seq = btab.shape[1]
    scale = (hd ** -0.5) if scale is None else scale

    kernel = functools.partial(
        _paged_attn_kernel,
        page_size=page_size,
        pages_per_seq=pages_per_seq,
        scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KVH, pages_per_seq),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, h, p, lens, bt: (b, h, 0, 0)),
            pl.BlockSpec(
                (1, 1, page_size, hd), lambda b, h, p, lens, bt: (h, bt[b, p], 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, page_size, hd), lambda b, h, p, lens, bt: (h, bt[b, p], 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, h, p, lens, bt: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lens, btab, q, k_pages, v_pages)
