"""Pallas TPU kernel: causal flash attention (training / prefill hot path).

Standard online-softmax tiling adapted for the MXU: ``(block_q, hd)`` query
tiles resident in VMEM while ``(block_k, hd)`` K/V tiles stream; the score
tile ``(block_q, block_k)`` hits the MXU twice per step (QK^T and PV). Blocks
default to 128 to match the 128x128 systolic array; f32 accumulation.

Causal handling: K-blocks entirely above the diagonal are masked to -inf and
contribute nothing. (A grid-skip via index rewriting is the classic further
optimization; masked blocks still cost MXU cycles. Recorded as a §Perf
candidate rather than done here -- correctness first.)

GQA: the wrapper folds the query-head group into the q rows, so K/V are never
materialized per-query-head: q (B, KVH, G*S, hd) against k (B, KVH, S, hd).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # (1, block_q, hd)
    k_ref,  # (1, block_k, hd)
    v_ref,  # (1, block_k, hd)
    o_ref,  # (1, block_q, hd)
    m_ref,  # scratch (block_q, 1) f32
    l_ref,  # scratch (block_q, 1) f32
    acc_ref,  # scratch (block_q, hd) f32
    *,
    scale: float,
    block_q: int,
    block_k: int,
    n_k_blocks: int,
    causal: bool,
    group: int,
):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # (block_q, block_k)

    if causal:
        # q rows are G interleaved copies of the sequence: logical position
        # of row r is (qi*block_q + r) // group.
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        q_pos = rows // group
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    pexp = jnp.exp(s - m_new)
    if causal:
        pexp = jnp.where(s <= NEG_INF / 2, 0.0, pexp)
    l_ref[...] = l_ref[...] * alpha + pexp.sum(axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        pexp, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ki == n_k_blocks - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (BH, Sq, hd)  -- Sq = G * S for GQA-folded queries
    k: jax.Array,  # (BH, Sk, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    group: int = 1,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    BH, Sq, hd = q.shape
    Sk = k.shape[1]
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, "pad seq to block multiple"
    scale = (hd ** -0.5) if scale is None else scale
    n_k_blocks = Sk // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        n_k_blocks=n_k_blocks,
        causal=causal,
        group=group,
    )
    return pl.pallas_call(
        kernel,
        grid=(BH, Sq // block_q, n_k_blocks),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, Sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(q, k, v)
