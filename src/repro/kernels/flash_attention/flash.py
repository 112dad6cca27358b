"""Pallas TPU flash-attention (forward) — §Perf iteration for LM cells.

Why it exists (EXPERIMENTS.md §Perf): the pure-JAX chunked attention keeps
the online softmax recurrence but still ROUND-TRIPS each (Bq, C) score
tile through HBM (matmul operands must materialise between XLA ops).  At
train_4k that score traffic dominates the memory roofline term (measured
~280 GB/device/step for tinyllama).  This kernel keeps the whole
(block_q × block_k) score tile in VMEM — the flash-attention recipe on
MXU tiles — reducing attention HBM traffic to the q/k/v/o tensors.

Grid: (B·H, Sq/block_q, Sk/block_k); k-dim innermost ("arbitrary") so the
(acc, m, l) state for one q-block stays resident across the k sweep.
Causality is handled per-tile: tiles fully above the diagonal contribute
nothing (masked), tiles fully below skip masking.

VMEM @ block_q=block_k=512, hd≤256, fp32 state:
  q 512·256·4 + k/v 2·512·256·4 + s 512·512·4 + acc 512·256·4 ≈ 3.6 MiB ≪ 16 MiB.

Backward is intentionally NOT a kernel here: training uses jax.checkpoint
around the jnp chunk body (recompute-in-bwd), which already avoids storing
scores; this kernel targets the forward/serving path and the §Perf
analysis.  (A full fwd+bwd kernel is the natural next iteration.)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, block_q, block_k, sk, causal):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0]                      # (block_q, hd)
    k = k_ref[0]                      # (block_k, hd)
    v = v_ref[0]
    hd = q.shape[-1]

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * (1.0 / (hd ** 0.5))           # (block_q, block_k)

    if causal:
        q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
        k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
        s = jnp.where(q_pos >= k_pos, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    m_ref[...] = m_new
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(kj == (sk // block_k) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "block_q", "block_k", "interpret")
)
def flash_attention(
    q: jnp.ndarray,   # (B, Sq, H, hd)
    k: jnp.ndarray,   # (B, Sk, H, hd) — kv pre-expanded to H heads
    v: jnp.ndarray,
    *,
    causal: bool = True,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)

    # layout: (B·H, S, hd) — head-major so one grid row owns one (b, h)
    qr = q.transpose(0, 2, 1, 3).reshape(b * h, sq, hd)
    kr = k.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)
    vr = v.transpose(0, 2, 1, 3).reshape(b * h, sk, hd)

    grid = (b * h, sq // block_q, sk // block_k)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, block_q=block_q, block_k=block_k, sk=sk, causal=causal
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, block_k, hd), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, block_k, hd), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda g, i, j: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qr, kr, vr)
    return out.reshape(b, h, sq, hd).transpose(0, 2, 1, 3)
