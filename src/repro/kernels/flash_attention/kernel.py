"""Block-wise online-softmax (flash) attention for the LM architectures.

Standard flash tiling adapted to the TPU memory hierarchy:

  grid = (batch * q_heads, sq / block_q, sk / block_k)
  dims = (parallel, parallel, arbitrary)  — kv dimension is sequential so
  the running (m, l, acc) state lives in VMEM scratch across kv steps.

GQA is handled with *index maps*, not materialized head repetition: the
k/v BlockSpecs map q-head h to kv-head h // group, so kv tiles for a group
of q heads are re-streamed from HBM but never duplicated there.

Causal masking compares global q/k positions (with the sk - sq decode
offset); fully-masked kv blocks are skipped cheaply via @pl.when on the
block-level causal bound, which halves work for the training shapes.

MXU alignment: block_q/block_k default to 128/256; head_dim is padded to a
multiple of 128 by ops.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 256
NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  offset: int, k_valid: int):
    """offset = sk_orig - sq_orig (decode); k_valid = sk before padding."""
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # block-level skip: first key of this block vs last query of the q
    # block (with decode offset), and key-padding bound
    q_last = qi * block_q + (block_q - 1) + offset
    k_first = kj * block_k
    live = k_first < k_valid
    if causal:
        live = live & (k_first <= q_last)

    @pl.when(live)
    def _body():
        q = q_ref[0].astype(jnp.float32)  # (bq, hd)
        k = k_ref[0].astype(jnp.float32)  # (bk, hd)
        v = v_ref[0].astype(jnp.float32)  # (bk, hd)
        s = jax.lax.dot(q, k.T,
                        precision=jax.lax.Precision.HIGHEST) * scale
        kpos = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = kpos < k_valid
        if causal:
            qpos = (qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)) + offset
            valid = valid & (kpos <= qpos)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]  # (bq, 1)
        l_prev = l_scr[...]
        m_cur = jnp.maximum(m_prev[:, 0], jnp.max(s, axis=1))[:, None]
        p = jnp.exp(s - m_cur)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_cur)  # (bq, 1)
        l_cur = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot(
            p, v, precision=jax.lax.Precision.HIGHEST)
        m_scr[...] = m_cur
        l_scr[...] = l_cur

    @pl.when(kj == nk - 1)
    def _fin():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_pallas(q: Array, k: Array, v: Array, *,
                           causal: bool = True,
                           scale: float | None = None,
                           offset: int | None = None,
                           k_valid: int | None = None,
                           block_q: int = DEFAULT_BLOCK_Q,
                           block_k: int = DEFAULT_BLOCK_K,
                           interpret: bool = True) -> Array:
    """q: (b, hq, sq, hd); k/v: (b, hkv, sk, hd). Shapes pre-padded.

    offset: original (sk - sq) BEFORE padding (decode alignment);
    k_valid: original sk BEFORE padding (padded keys are masked out).
    """
    b, hq, sq, hd = q.shape
    _, hkv, sk, _ = k.shape
    assert hq % hkv == 0, (hq, hkv)
    group = hq // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0
    scale = (hd ** -0.5) if scale is None else scale
    offset = (sk - sq) if offset is None else offset
    k_valid = sk if k_valid is None else k_valid

    qf = q.reshape(b * hq, sq, hd)
    kf = k.reshape(b * hkv, sk, hd)
    vf = v.reshape(b * hkv, sk, hd)
    grid = (b * hq, sq // block_q, sk // block_k)

    def kv_index(h, qi, kj):
        # q-head h lives in batch h // hq; its kv head is (h % hq) // group
        return ((h // hq) * hkv + (h % hq) // group, kj, 0)

    kernel = functools.partial(_flash_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k,
                               offset=offset, k_valid=k_valid)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda h, qi, kj: (h, qi, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd),
                               lambda h, qi, kj: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, hd)
