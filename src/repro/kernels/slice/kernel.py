"""Fused Pallas TPU kernel for frozen-table slice queries (DESIGN.md §12).

One ``pallas_call`` runs the whole per-query pipeline — hash probe of the
d+1 enclosing vertices, dense-row translation, table gather, barycentric
contraction, miss accumulation — with ALL frozen state resident in VMEM
for the whole grid (the blur kernels' constant-index-map pattern):

  resident   tkeys (hcap, npk), row_of_slot (hcap, 1), tables (m+1, c)
  streamed   per query block: packed vertex keys + precomputed home
             slots + active mask ((block_b*(d+1), .) rows, query-major)
             and barycentric weights (block_b, d+1)
  out        (block_b, c) sliced values + (block_b, 1) miss mass

The probe loop is the vectorized lookup of ``kernels/hash/kernel.py``:
each round is one gather + compare over the block's (d+1)-vertex rows,
stopping per lane at a key match or an empty slot (KEY_SENTINEL — no
deletions, so emptiness proves absence). A serving batch therefore costs
zero HBM round-trips between lookup and slice, versus 2 kernel dispatches
plus an (b*(d+1), c) HBM intermediate on the unfused path.

Off-TPU the interpreter is opt-in (interpret=True), matching the blur and
hash kernels' convention; ops.py dispatches to the XLA reference instead.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hash.ref import KEY_SENTINEL, initial_slots

Array = jax.Array

DEFAULT_BLOCK_B = 256


def _slice_kernel(tk_ref, s2r_ref, tab_ref, q_ref, h_ref, act_ref, w_ref,
                  out_ref, miss_ref, *, hcap: int, m: int, dp1: int,
                  sentinel: int):
    """One block of queries against the resident index + tables."""
    tk = tk_ref[...]  # (hcap, npk) — resident gather source
    q = q_ref[...]  # (block_b*dp1, npk)
    slot = h_ref[...][:, 0]
    active = act_ref[...][:, 0] != 0
    mask = hcap - 1

    def cond(st):
        _, _, done, k = st
        return jnp.logical_and(k < hcap, ~jnp.all(done))

    def body(st):
        slot_, res, done, k = st
        row = jnp.take(tk, slot_, axis=0)  # (block_b*dp1, npk)
        hit = ~done & jnp.all(row == q, axis=1)
        empty = ~done & (row[:, 0] == sentinel)
        res = jnp.where(hit, slot_, res)
        done = done | hit | empty
        slot_ = jnp.where(done, slot_, (slot_ + 1) & mask)
        return slot_, res, done, k + 1

    res0 = jnp.full(slot.shape, -1, jnp.int32)
    _, res, _, _ = jax.lax.while_loop(
        cond, body, (slot, res0, ~active, jnp.int32(0)))

    s2r = s2r_ref[...][:, 0]  # (hcap,)
    row = jnp.where(res >= 0, jnp.take(s2r, jnp.clip(res, 0, hcap - 1)), m)
    tab = tab_ref[...]  # (m+1, c)
    vals = jnp.take(tab, row, axis=0)  # (block_b*dp1, c)
    w = w_ref[...].astype(tab.dtype)  # (block_b, dp1)
    bb = w.shape[0]
    absent = (row == m).astype(tab.dtype)

    # query-major rows: vertex k of query i sits at i*dp1 + k
    base = jax.lax.broadcasted_iota(jnp.int32, (bb, 1), 0)[:, 0] * dp1
    out = jnp.zeros((bb, tab.shape[1]), tab.dtype)
    miss = jnp.zeros((bb,), tab.dtype)
    for k in range(dp1):
        out = out + w[:, k][:, None] * jnp.take(vals, base + k, axis=0)
        miss = miss + w[:, k] * jnp.take(absent, base + k)
    out_ref[...] = out
    # clip to the documented [0, 1] contract (f32 weight sums are 1 +/- eps)
    miss_ref[...] = jnp.clip(miss, 0.0, 1.0)[:, None]


def _slice_tangent_kernel(tk_ref, s2r_ref, tab_ref, q_ref, h_ref, act_ref,
                          w_ref, wd_ref, out_ref, outd_ref, miss_ref, *,
                          hcap: int, m: int, dp1: int, sentinel: int):
    """Primal + directional-tangent slice block (DESIGN.md §15).

    Identical probe to ``_slice_kernel``; the gathered table rows feed TWO
    barycentric contractions — against the weights and against their
    directional derivative — so the query-space JVP costs zero extra
    probes or gathers over the primal.
    """
    tk = tk_ref[...]
    q = q_ref[...]
    slot = h_ref[...][:, 0]
    active = act_ref[...][:, 0] != 0
    mask = hcap - 1

    def cond(st):
        _, _, done, k = st
        return jnp.logical_and(k < hcap, ~jnp.all(done))

    def body(st):
        slot_, res, done, k = st
        row = jnp.take(tk, slot_, axis=0)
        hit = ~done & jnp.all(row == q, axis=1)
        empty = ~done & (row[:, 0] == sentinel)
        res = jnp.where(hit, slot_, res)
        done = done | hit | empty
        slot_ = jnp.where(done, slot_, (slot_ + 1) & mask)
        return slot_, res, done, k + 1

    res0 = jnp.full(slot.shape, -1, jnp.int32)
    _, res, _, _ = jax.lax.while_loop(
        cond, body, (slot, res0, ~active, jnp.int32(0)))

    s2r = s2r_ref[...][:, 0]
    row = jnp.where(res >= 0, jnp.take(s2r, jnp.clip(res, 0, hcap - 1)), m)
    tab = tab_ref[...]
    vals = jnp.take(tab, row, axis=0)
    w = w_ref[...].astype(tab.dtype)
    wd = wd_ref[...].astype(tab.dtype)
    bb = w.shape[0]
    absent = (row == m).astype(tab.dtype)

    base = jax.lax.broadcasted_iota(jnp.int32, (bb, 1), 0)[:, 0] * dp1
    out = jnp.zeros((bb, tab.shape[1]), tab.dtype)
    out_d = jnp.zeros((bb, tab.shape[1]), tab.dtype)
    miss = jnp.zeros((bb,), tab.dtype)
    for k in range(dp1):
        v = jnp.take(vals, base + k, axis=0)
        out = out + w[:, k][:, None] * v
        out_d = out_d + wd[:, k][:, None] * v
        miss = miss + w[:, k] * jnp.take(absent, base + k)
    out_ref[...] = out
    outd_ref[...] = out_d
    miss_ref[...] = jnp.clip(miss, 0.0, 1.0)[:, None]


def slice_query_tangent_pallas(tkeys: Array, row_of_slot: Array,
                               tables: Array, q_packed: Array,
                               weights: Array, weights_dot: Array,
                               active: Array, *,
                               block_b: int = DEFAULT_BLOCK_B,
                               interpret: bool = False
                               ) -> tuple[Array, Array, Array]:
    """Fused lookup + primal/tangent slice; contract of
    ``ref.slice_query_tangent_xla``."""
    hcap, npk = tkeys.shape
    b, dp1 = weights.shape
    m1, c = tables.shape
    h0 = initial_slots(q_packed, hcap)[:, None]
    act = active.astype(jnp.int32)[:, None]
    pad = (-b) % block_b
    if pad:
        q_packed = jnp.concatenate(
            [q_packed, jnp.zeros((pad * dp1, npk), q_packed.dtype)], axis=0)
        h0 = jnp.concatenate([h0, jnp.zeros((pad * dp1, 1), h0.dtype)])
        act = jnp.concatenate([act, jnp.zeros((pad * dp1, 1), act.dtype)])
        weights = jnp.concatenate(
            [weights, jnp.zeros((pad, dp1), weights.dtype)], axis=0)
        weights_dot = jnp.concatenate(
            [weights_dot, jnp.zeros((pad, dp1), weights_dot.dtype)], axis=0)
    padded = b + pad

    kernel = functools.partial(_slice_tangent_kernel, hcap=hcap, m=m1 - 1,
                               dp1=dp1, sentinel=int(KEY_SENTINEL))
    resident = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))  # noqa: E731
    out, out_d, miss = pl.pallas_call(
        kernel,
        grid=(padded // block_b,),
        in_specs=[
            resident((hcap, npk)),  # tkeys
            resident((hcap, 1)),  # row_of_slot
            resident((m1, c)),  # tables
            pl.BlockSpec((block_b * dp1, npk), lambda i: (i, 0)),
            pl.BlockSpec((block_b * dp1, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b * dp1, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, dp1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, dp1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_b, c), lambda i: (i, 0)),
            pl.BlockSpec((block_b, c), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((padded, c), tables.dtype),
            jax.ShapeDtypeStruct((padded, c), tables.dtype),
            jax.ShapeDtypeStruct((padded, 1), tables.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tkeys, row_of_slot.reshape(-1, 1), tables, q_packed, h0, act,
      weights, weights_dot)
    return out[:b], out_d[:b], miss[:b, 0]


def slice_query_pallas(tkeys: Array, row_of_slot: Array, tables: Array,
                       q_packed: Array, weights: Array, active: Array, *,
                       block_b: int = DEFAULT_BLOCK_B,
                       interpret: bool = False) -> tuple[Array, Array]:
    """Fused lookup+slice; same contract as ``ref.slice_query_xla``."""
    hcap, npk = tkeys.shape
    b, dp1 = weights.shape
    m1, c = tables.shape
    h0 = initial_slots(q_packed, hcap)[:, None]
    act = active.astype(jnp.int32)[:, None]
    pad = (-b) % block_b
    if pad:
        q_packed = jnp.concatenate(
            [q_packed, jnp.zeros((pad * dp1, npk), q_packed.dtype)], axis=0)
        h0 = jnp.concatenate([h0, jnp.zeros((pad * dp1, 1), h0.dtype)])
        act = jnp.concatenate([act, jnp.zeros((pad * dp1, 1), act.dtype)])
        weights = jnp.concatenate(
            [weights, jnp.zeros((pad, dp1), weights.dtype)], axis=0)
    padded = b + pad

    kernel = functools.partial(_slice_kernel, hcap=hcap, m=m1 - 1, dp1=dp1,
                               sentinel=int(KEY_SENTINEL))
    resident = lambda shape: pl.BlockSpec(shape, lambda i: (0,) * len(shape))  # noqa: E731
    out, miss = pl.pallas_call(
        kernel,
        grid=(padded // block_b,),
        in_specs=[
            resident((hcap, npk)),  # tkeys
            resident((hcap, 1)),  # row_of_slot
            resident((m1, c)),  # tables
            pl.BlockSpec((block_b * dp1, npk), lambda i: (i, 0)),
            pl.BlockSpec((block_b * dp1, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b * dp1, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_b, dp1), lambda i: (i, 0)),
        ],
        out_specs=(
            pl.BlockSpec((block_b, c), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 1), lambda i: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((padded, c), tables.dtype),
            jax.ShapeDtypeStruct((padded, 1), tables.dtype),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tkeys, row_of_slot.reshape(-1, 1), tables, q_packed, h0, act,
      weights)
    return out[:b], miss[:b, 0]
