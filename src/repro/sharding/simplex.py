"""Data-parallel Simplex-GP: the sharded lattice MVM (DESIGN.md §10).

The splat→blur→slice operator decomposes cleanly over devices because the
two big per-*point* objects (inputs and values) and the one per-*lattice-
point* object (the deduped value table) have wildly different sizes: the
table is m ≲ n(d+1) rows but in practice a small fraction of it (paper
Table 3), so it is cheap to REPLICATE, while the n data rows are what
actually scale — so they are SHARDED:

  splat   local segment-sum of the device's (n/dev)(d+1) contributions
          into a full-size (cap+1, c) table, then ONE ``psum`` — the only
          collective of the whole MVM;
  blur    the 2(d+1) directional sweeps run replicated on the summed
          table (identical work per device; no communication);
  slice   purely local — each device gathers table rows for its own
          points via its shard of ``seg_ids``/barycentric weights.

The per-point lattice arrays (``seg_ids``, ``weights``) carry *global*
slot ids in [0, cap], so sharding them by point rows needs no re-indexing.
The lattice is built once, globally (the build is already amortized to one
per step — DESIGN.md §9); this module distributes the per-iteration MVMs,
which is where CG/mBCG/LOVE spend their time.

One-psum-per-MVM is a hard contract: ``count_primitive`` below lets tests
and benchmarks assert it on the jaxpr (``symmetrize`` reuses the same
summed table for both sweep orders, so it adds no collective).

Build-backend interplay (DESIGN.md §11): the sharded MVM is agnostic to
which build path produced the ``Lattice`` — ``seg_ids`` carry *global*
slot ids and the blur graph is a dense gather table under every backend
(sort's lex numbering vs the hash build's placement numbering are related
by a pure slot permutation, which the replicated table absorbs). What
must NOT happen is mixing lattices across paths for the same point set:
consumers holding slot-indexed state (the replicated ``nbr`` table, LOVE
caches) would silently mix numberings — ``LatticeCache`` therefore keys
on the build backend alongside the device/sharding layout.

Everything is plain XLA inside ``shard_map`` — on CPU hosts with
``--xla_force_host_platform_device_count=8`` the sharded path is
bit-compatible modulo f32 summation order with the single-device
``fused_xla`` tier, which is exactly what tests/test_multidevice.py pins.
"""
from __future__ import annotations

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from repro import obs
from repro.core.lattice import Lattice, slice_rows, splat_rows

Array = jax.Array


def data_mesh(num_devices: int | None = None,
              axis_name: str = "data") -> Mesh:
    """1-D device mesh over (a prefix of) the available devices."""
    devs = jax.devices()
    if num_devices is not None:
        devs = devs[:num_devices]
    return Mesh(np.array(devs), (axis_name,))


def shard_rows(n: int, mesh: Mesh, axis_name: str) -> tuple[int, int]:
    """(rows per device, ghost rows) for n points on the axis — ANY n.

    There is deliberately no divisibility requirement (the old hard error
    was the "divisibility cliff"): ``sharded_lattice_mvm`` pads the three
    per-point arrays with ``ghost`` zero-weight rows so every device gets
    an equal shard. Ghost rows carry barycentric weight 0 and splat into
    the trash row ``cap`` (which the blur zeroes anyway), so the masked
    segment-sum is bit-equivalent to the unpadded operator on the real
    rows — including n < axis size, where some devices hold only ghosts.
    """
    ndev = int(mesh.shape[axis_name])
    ghost = (-n) % ndev
    return (n + ghost) // ndev, ghost


def check_shardable(n: int, mesh: Mesh, axis_name: str) -> int:
    """Points-per-device under ghost padding (kept for API compatibility).

    Historically raised on indivisible n; since the elastic-training work
    any n shards (zero-weight ghost rows make up the remainder), so this
    now just reports the padded per-device row count.
    """
    return shard_rows(n, mesh, axis_name)[0]


def sharded_lattice_mvm(lat: Lattice, v: Array, weights: Array | None = None,
                        *, mesh: Mesh, axis_name: str = "data",
                        taps: tuple[float, ...] | None = None,
                        symmetrize: bool = True,
                        transpose: bool = False) -> Array:
    """W B W^T v with rows of ``v`` sharded over ``mesh``; one psum total.

    Semantically identical to the single-device ``kernels.blur.ops``
    backends (same linear operator; summation order differs only across
    device boundaries, so results agree to f32 accumulation noise).
    ``weights`` may be traced (the sharded path is pure XLA).

    Any n shards: when n does not divide the axis size, the per-point
    arrays are padded with GHOST rows — zero values, zero barycentric
    weight, seg_id = the trash row ``cap``. A ghost contributes exactly
    0.0 to the segment-sum of a row the blur zeroes regardless, so the
    real rows' results are bit-identical to the pad-free layout (and for
    divisible n no padding code runs at all). Padding happens outside
    ``shard_map``, so the one-psum contract is untouched.
    """
    if weights is None:
        if taps is None:
            raise ValueError("sharded_lattice_mvm needs weights= or taps=")
        weights = jnp.asarray(taps, v.dtype)
    n, c = v.shape
    if n != lat.n:
        raise ValueError(f"v has {n} rows but the lattice was built for "
                         f"{lat.n} points")
    _, ghost = shard_rows(n, mesh, axis_name)
    d1 = lat.d + 1
    r = lat.r
    cap = lat.cap
    # (n, d+1) layout so the per-point leading axis is the sharded one.
    seg = lat.seg_ids.reshape(lat.n, d1)
    bary = lat.weights
    if ghost:
        v = jnp.concatenate(
            [v, jnp.zeros((ghost, c), v.dtype)], axis=0)
        seg = jnp.concatenate(
            [seg, jnp.full((ghost, d1), cap, seg.dtype)], axis=0)
        bary = jnp.concatenate(
            [bary, jnp.zeros((ghost, d1), bary.dtype)], axis=0)

    def local_mvm(v_loc, seg_loc, bw_loc, nbr, w):
        # --- splat (local) + the ONE collective --------------------------
        with obs.scope("mvm.splat"):
            table = splat_rows(v_loc, seg_loc, bw_loc, cap + 1)
        with obs.scope("mvm.psum"):
            table = jax.lax.psum(table, axis_name)
        table = table.at[cap].set(0.0)

        # --- blur (replicated on the summed table) -----------------------
        with obs.scope("mvm.blur"):
            w_off = jnp.concatenate([w[:r], w[r + 1:]])  # (2r,) off-center

            def blur_dir(vals, a):
                out = vals * w[r] + jnp.einsum("prc,r->pc", vals[nbr[a]],
                                               w_off)
                return out.at[cap].set(0.0), None

            order = jnp.arange(d1)
            fwd = order[::-1] if transpose else order
            blurred, _ = jax.lax.scan(blur_dir, table, fwd)
            if symmetrize:  # 0.5 (F + F^T): same summed table, opposite sweep
                blurred_r, _ = jax.lax.scan(blur_dir, table, fwd[::-1])
                blurred = 0.5 * (blurred + blurred_r)

        # --- slice (local) ----------------------------------------------
        with obs.scope("mvm.slice"):
            return slice_rows(blurred, seg_loc, bw_loc)

    fn = shard_map(
        local_mvm, mesh=mesh,
        in_specs=(P(axis_name, None), P(axis_name, None),
                  P(axis_name, None), P(), P()),
        out_specs=P(axis_name, None))
    with obs.scope("mvm"):
        out = fn(v, seg, bary, lat.nbr, weights.astype(v.dtype))
    return out[:n] if ghost else out


def mesh_fingerprint(mesh) -> str:
    """Hashable identity of a device mesh for cache keys (DESIGN.md §16).

    Two meshes are interchangeable for a consumer holding mesh-dependent
    compiled/sharded state ONLY if they have the same axis layout over the
    same physical devices — so the fingerprint is (axis names/sizes, the
    flattened device ids). ``None`` (no mesh — single-device execution)
    fingerprints as "". ``LatticeCache`` folds this into its key so a
    lattice produced for one mesh layout is NEVER served to an MVM running
    on a different one after an elastic resize (8→4 must rebuild).
    """
    if mesh is None:
        return ""
    shape = tuple((str(name), int(size))
                  for name, size in mesh.shape.items())
    devs = tuple(int(d.id) for d in np.asarray(mesh.devices).reshape(-1))
    return f"{shape}|{devs}"


# NOTE: there is deliberately no sharded twin of ``filtering.mvm_operator``
# here — pass ``mesh=`` to it (or to ``SimplexGP.operator``) and its matvec
# dispatches to ``sharded_lattice_mvm`` while keeping the cache/auto-cap
# machinery of DESIGN.md §9.


# ---------------------------------------------------------------------------
# Replicated-table serving contract (DESIGN.md §12).
# ---------------------------------------------------------------------------
# The frozen serving path inverts the training MVM's sharding economics:
# training shards the n data rows and replicates the small value table with
# ONE psum per MVM, but a frozen-predictor query touches no shared
# accumulator at all — every query is an independent hash-probe + gather +
# contraction against immutable tables. So the serving contract is:
#
#   frozen state (hash index + value tables + hyperparameters) REPLICATED,
#   query rows SHARDED over the data axis, outputs sharded the same way,
#   ZERO collectives (assert with ``collective_counts``).
#
# Throughput therefore scales linearly in devices for batches that divide
# the axis (gp/serve.predict pads its buckets to the axis size). Keeping
# the tables replicated is cheap for the same reason the blur table is:
# they hold m + 1 <= cap + 1 rows, a small fraction of n(d+1) in practice.


# Hot-swap contract (DESIGN.md §13): the serving engine may PUBLISH a new
# frozen state while traffic is in flight. That is safe under this
# replicated contract because (a) a Predictor is an immutable pytree — a
# query batch that grabbed the old reference keeps serving the old
# version end to end (no torn reads: nothing is mutated in place), and
# (b) the swap itself is a host-side reference assignment AFTER the
# candidate has been fully materialized on every device via
# ``replicate_pytree`` and validated (serve.validate_predictor) — devices
# never observe a half-transferred table. Per-bucket compilations key on
# array shapes, not identities, so a swap whose (n, m, k) are unchanged
# (the y-only refresh path) reuses every compiled bucket.


def replicate_pytree(tree, mesh: Mesh):
    """Place every array leaf of ``tree`` fully replicated on ``mesh``.

    The publish step of the hot-swap contract above: a candidate frozen
    state is replicated here BEFORE the registry swap, so the first
    post-swap query pays no lazy per-device transfer (and a transfer
    failure surfaces at publish time — refusable — instead of on the
    query path)."""
    sharding = jax.sharding.NamedSharding(mesh, P())

    def place(leaf):
        return jax.device_put(leaf, sharding) \
            if isinstance(leaf, jax.Array) else leaf

    return jax.tree.map(place, tree)


def replicated_table_serve(fn, mesh: Mesh, axis_name: str = "data"):
    """Wrap ``fn(frozen_state, queries) -> per-query outputs`` for
    replicated-table serving: returns a JITTED callable with the frozen
    state replicated, query rows sharded over ``axis_name``, and every
    output sharded the same way. ``fn`` must be embarrassingly parallel
    over query rows (no cross-query reductions) — which is exactly what
    the frozen slice path is."""
    # check_vma=False: replication is by construction here (the frozen
    # state is P() everywhere and nothing reduces across queries), so the
    # varying-manual-axes type check has nothing to prove.
    sharded = shard_map(fn, mesh=mesh, in_specs=(P(), P(axis_name)),
                        out_specs=P(axis_name), check_vma=False)
    return jax.jit(sharded)


# ---------------------------------------------------------------------------
# Collective-count inspection (the one-psum contract).
# ---------------------------------------------------------------------------

COLLECTIVE_PRIMITIVES = ("psum", "all_gather", "all_to_all", "ppermute",
                         "psum_scatter")

# inside shard_map bodies jax names the reduction primitive
# "psum_invariant" (its result is the same on every device); count it as a
# psum — it IS the cross-device all-reduce. "pvary" is replication
# bookkeeping with no communication and is deliberately not counted.
_PRIMITIVE_ALIASES = {"psum_invariant": "psum"}
_JAXPR_TYPES = (jax.extend.core.Jaxpr, jax.extend.core.ClosedJaxpr)


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` in a (closed) jaxpr, recursively
    descending into sub-jaxprs (scan/while bodies, shard_map, pjit)."""
    core_jaxpr = getattr(jaxpr, "jaxpr", jaxpr)
    total = 0
    for eqn in core_jaxpr.eqns:
        if _PRIMITIVE_ALIASES.get(eqn.primitive.name,
                                  eqn.primitive.name) == name:
            total += 1
        for v in eqn.params.values():
            for sub in jax.tree_util.tree_leaves(
                    v, is_leaf=lambda x: isinstance(x, _JAXPR_TYPES)):
                if isinstance(sub, _JAXPR_TYPES):
                    total += count_primitive(sub, name)
    return total


def collective_counts(fn, *args) -> dict[str, int]:
    """{primitive: count} over ``COLLECTIVE_PRIMITIVES`` for ``fn(*args)``."""
    jaxpr = jax.make_jaxpr(fn)(*args)
    return {p: count_primitive(jaxpr, p) for p in COLLECTIVE_PRIMITIVES}


# ---------------------------------------------------------------------------
# Gradient contract of replicated-table serving (DESIGN.md §15).
# ---------------------------------------------------------------------------
# The zero-collective serving contract EXTENDS to query-space gradients:
# d(mean, var)/d(x*) of the frozen slice is, per query, the same local
# probe + gather + contraction against analytic weight derivatives — no
# cross-query term exists, so differentiating w.r.t. the SHARDED queries
# introduces no communication. The only way a collective could appear is
# a cotangent w.r.t. the REPLICATED frozen state (summing per-device
# table cotangents needs a psum); serving gradients never request that —
# the tables are frozen constants, so ``jax.grad(..., argnums=queries)``
# partial-evaluates the table cotangent away. ``assert_zero_collectives``
# pins this on the gradient jaxpr (tests/test_serve_grad.py and
# benchmarks/fig_rollout.py both assert it).


def assert_zero_collectives(fn, *args, what: str = "serving") -> None:
    """Raise if ``fn(*args)`` would execute ANY collective primitive.

    Traces (never runs) ``fn`` and counts ``COLLECTIVE_PRIMITIVES`` in
    the jaxpr, recursively. Use on serving entry points and on their
    gradient functions to enforce the zero-collective contracts above.
    """
    counts = {p: c for p, c in collective_counts(fn, *args).items() if c}
    if counts:
        raise AssertionError(
            f"zero-collective {what} contract violated: found {counts}")
