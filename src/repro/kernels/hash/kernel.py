"""Pallas TPU kernels for the open-addressing lattice hash table.

Two kernels, mirroring the CUDA hash table of the paper's implementation
(Adams et al. 2010 / Simplex-GP) under TPU constraints:

  * ``hash_lookup_pallas`` — the neighbor-resolution hot path. Fully
    vectorized: the materialized key table stays VMEM-resident across the
    whole grid (constant index_map, like the blur kernels' gather
    source), queries stream in blocks, and each probe round is one
    vectorized gather + compare over the block. Probing stops per lane
    at a key match or an empty slot (KEY_SENTINEL: no deletions, so an
    empty slot proves absence).

  * ``hash_insert_pallas`` — the dedup phase. TPUs have no atomics, but a
    Pallas grid runs *sequentially* on a core, so insertion needs no CAS
    at all: a single program walks the rows in order, probing the
    VMEM-resident ``owner`` table and claiming the first empty slot with
    a plain store. This is scalar-throughput bound (one row at a time)
    and is honest about it — the XLA fallback (ref.py) stays the default
    where the epoch-vectorized insert wins; this kernel exists for
    TPU-resident builds where keeping the table in VMEM and avoiding
    HBM scatter round-trips dominates.

Both kernels take PACKED key rows (int32 words) and are agnostic to the
lattice geometry; hashing runs outside (ref.hash32) so the two backends
share one hash function bit-for-bit. Off-TPU the interpreter is opt-in
(interpret=True), matching kernels/blur's convention.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.hash.ref import KEY_SENTINEL, initial_slots

Array = jax.Array

DEFAULT_BLOCK_Q = 1024


# ---------------------------------------------------------------------------
# Vectorized lookup.
# ---------------------------------------------------------------------------


def _lookup_kernel(tk_ref, q_ref, h_ref, act_ref, out_ref, *, hcap: int,
                   sentinel: int):
    """One block of queries against the resident key table."""
    tk = tk_ref[...]  # (hcap, npk) — resident gather source
    q = q_ref[...]  # (block_q, npk)
    slot = h_ref[...][:, 0]  # (block_q,) precomputed home slots
    active = act_ref[...][:, 0] != 0
    mask = hcap - 1

    def cond(st):
        _, _, done, k = st
        return jnp.logical_and(k < hcap, ~jnp.all(done))

    def body(st):
        slot_, res, done, k = st
        row = jnp.take(tk, slot_, axis=0)  # (block_q, npk)
        hit = ~done & jnp.all(row == q, axis=1)
        miss = ~done & (row[:, 0] == sentinel)
        res = jnp.where(hit, slot_, res)
        done = done | hit | miss
        slot_ = jnp.where(done, slot_, (slot_ + 1) & mask)
        return slot_, res, done, k + 1

    res0 = jnp.full(slot.shape, -1, jnp.int32)
    _, res, _, _ = jax.lax.while_loop(
        cond, body, (slot, res0, ~active, jnp.int32(0)))
    out_ref[...] = res[:, None]


def hash_lookup_pallas(tkeys: Array, queries: Array, active: Array, *,
                       block_q: int = DEFAULT_BLOCK_Q,
                       interpret: bool = False) -> Array:
    """Slot of each query key, or -1 (absent / inactive). tkeys resident."""
    hcap, npk = tkeys.shape
    nq = queries.shape[0]
    h0 = initial_slots(queries, hcap)[:, None]
    act = active.astype(jnp.int32)[:, None]
    pad = (-nq) % block_q
    if pad:
        queries = jnp.concatenate(
            [queries, jnp.zeros((pad, npk), queries.dtype)], axis=0)
        h0 = jnp.concatenate([h0, jnp.zeros((pad, 1), h0.dtype)], axis=0)
        act = jnp.concatenate([act, jnp.zeros((pad, 1), act.dtype)], axis=0)
    padded = nq + pad

    kernel = functools.partial(_lookup_kernel, hcap=hcap,
                               sentinel=int(KEY_SENTINEL))
    out = pl.pallas_call(
        kernel,
        grid=(padded // block_q,),
        in_specs=[
            pl.BlockSpec((hcap, npk), lambda i: (0, 0)),  # resident table
            pl.BlockSpec((block_q, npk), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
            pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_q, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded, 1), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(tkeys, queries, h0, act)
    return out[:nq, 0]


# ---------------------------------------------------------------------------
# Sequential-core insert.
# ---------------------------------------------------------------------------

# per-row probe outcomes inside the insert kernel
_CONTINUE = 0
_FOUND = 1
_CLAIM = 2
_FULL = 3


def _insert_kernel(pk_ref, h_ref, owner_ref, slot_ref, ok_ref, *,
                   hcap: int, n_rows: int):
    """Serial open-addressing insert; the grid is one sequential program."""
    empty = jnp.int32(n_rows)
    mask = hcap - 1
    owner_ref[...] = jnp.full((hcap, 1), empty, jnp.int32)

    def row_body(i, carry):
        key = pk_ref[pl.dslice(i, 1), :]  # (1, npk)
        h = h_ref[i, 0]

        def cond(st):
            _, state, _ = st
            return state == _CONTINUE

        def body(st):
            slot, state, k = st
            own = owner_ref[slot, 0]
            is_empty = own == empty
            okey = pk_ref[pl.dslice(jnp.where(is_empty, 0, own), 1), :]
            match = jnp.logical_and(~is_empty, jnp.all(okey == key))
            state = jnp.where(match, _FOUND,
                              jnp.where(is_empty, _CLAIM,
                                        jnp.where(k + 1 >= hcap, _FULL,
                                                  _CONTINUE)))
            slot = jnp.where(state == _CONTINUE, (slot + 1) & mask, slot)
            return slot, state, k + 1

        slot, state, _ = jax.lax.while_loop(
            cond, body, (h, jnp.int32(_CONTINUE), jnp.int32(0)))

        # claim-after-probe: execution is sequential, so the store cannot
        # race with any other row's probe
        @pl.when(state == _CLAIM)
        def _claim():
            owner_ref[slot, 0] = i

        slot_ref[i, 0] = slot
        ok_ref[i, 0] = jnp.where(state == _FULL, 0, 1)
        return carry

    jax.lax.fori_loop(0, n_rows, row_body, jnp.int32(0))


def hash_insert_pallas(packed: Array, hcap: int, *,
                       interpret: bool = False):
    """Serial insert of all N packed key rows. Same contract as
    ``ref.hash_insert_xla`` (owner, slot, ok); slot assignment may differ
    (first-come claims instead of min-row-id epoch claims) — the build's
    equivalence is up to slot permutation either way."""
    n_rows, npk = packed.shape
    h0 = initial_slots(packed, hcap)[:, None]
    owner, slot, ok = pl.pallas_call(
        functools.partial(_insert_kernel, hcap=hcap, n_rows=n_rows),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((hcap, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_rows, 1), jnp.int32),
            jax.ShapeDtypeStruct((n_rows, 1), jnp.int32),
        ),
        interpret=interpret,
    )(packed, h0)
    return owner[:, 0], slot[:, 0], ok[:, 0] != 0
