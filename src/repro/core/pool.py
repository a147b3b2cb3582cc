"""Subtree-blocked memory pool (Plane B): the paper's level-M placement.

The paper stores every subtree rooted at level M on a single memory server
(§3 Index Placement) so offloaded traversals never chase pointers across
servers.  On a TPU mesh the equivalent is a *blocked* layout:

    pool_keys    : Words of [n_subtrees, subtree_cap * FANOUT]  -- axis 0
    pool_children:          [n_subtrees, subtree_cap * FANOUT]     sharded
    pool_values  : Words of [n_subtrees, subtree_cap * FANOUT]     over `model`

with all levels above M ("top tree") replicated on every chip — these are
the paper's root-side nodes that are effectively always cached.  Local node
ids inside a subtree are level-ordered (root = 0) so the offload executor
(and the Pallas ``subtree_walk`` kernel) can traverse entirely within one
VMEM-resident block.

**Block rows.**  A subtree block's node rows lie end to end in one
``subtree_cap * FANOUT`` row: node ``(s, l)`` is the slice ``[l * FANOUT,
(l + 1) * FANOUT)`` of row ``s``; :func:`node_rows` / :func:`set_node_rows`
read and write it and :func:`blocks` gives the ``[S, C, FANOUT]`` view.  A
``[S, C, 64]`` array has no lane-dense TPU layout (64 pads to 128 lanes), so
XLA lays such a pool out subtree-minor and re-lays the whole of it out for
every row gather and scatter (about three pool-sized temporaries per
engine step by the TPU compiler's memory analysis).  Block rows are
a multiple of 128 lanes, and a row gather reads 64 lanes in place.

**Word planes.**  The int64 key and value planes are each stored as two
int32 planes of the same ``[S, C * FANOUT]`` shape, a :class:`Words` pair:
``hi`` is ``x >> 32``, ``lo`` the low 32 bits reinterpreted as int32
(:func:`split_words` / :func:`join_words`; ``KEY_MAX`` is ``(0x7FFFFFFF,
-1)``).  A TPU has no 64-bit lanes: an int64 plane in the engine's state
made the compiler split the whole of it into two 32-bit halves and combine
them back on every step, three pool-sized passes to serve a few thousand
rows.  Each word is a plane of its own, laid out as ``pool_children`` is:
a stacked ``[2, S, C * FANOUT]`` array gets another default layout on the
TPU than the step's row loops use, and the step then copied the whole of it
into theirs and back.  :func:`node_rows` gathers both words of a row with
the one index and joins them to int64 after the gather;
:func:`set_node_rows` splits rows before its scatter.  Nothing joins or
splits a whole plane inside a compiled step; host code reads the int64 view
through :func:`keys_i64` / :func:`values_i64`.

**Free-list headroom (the on-mesh SMO allocation layer).**  Each block is
built ``headroom`` fraction larger than the bulk layout needs; the extra
slots ``[base_cap, subtree_cap)`` form a per-subtree bump free-list from
which the on-mesh SMO engine (core/smo.py) allocates sibling nodes for
device-side leaf/inner splits.  The watermark lives in
``DexState.n_alloc`` (one int per subtree, sharded with the pool); when a
subtree's watermark hits ``subtree_cap`` its splits fall back to the host
rebuild path (``core/write.py::drain_splits``).  Because splits relocate
leaves out of the dense bulk order, sibling-leaf iteration (core/scan.py)
follows the explicit successor table seeded by :func:`initial_succ` rather
than leaf-id arithmetic.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.nodes import FANOUT, KEY_MAX, KEY_MIN, NULL


class Words(NamedTuple):
    """An int64 array stored as its two int32 words (see the module
    docstring): ``hi = x >> 32`` and ``lo`` = the low 32 bits as int32."""

    hi: jax.Array
    lo: jax.Array


class SubtreePool(NamedTuple):
    """Pool arrays.  ``top_*`` are replicated; ``pool_*`` shard on axis 0."""

    # top tree (levels > M), flat ids in build order, root last
    top_keys: jax.Array       # [T, FANOUT] int64
    top_children: jax.Array   # [T, FANOUT] int32; at level M+1 the entries
                              # are *subtree ids* (pool axis-0 indices)
    # subtree blocks (levels M..0) as block rows; the int64 keys and values
    # as int32 word planes (see module docstring)
    pool_keys: Words          # 2 x [S, C * FANOUT] int32 words of int64 keys
    pool_children: jax.Array  # [S, C * FANOUT] int32 (subtree-local ids)
    pool_values: Words        # 2 x [S, C * FANOUT] int32 words of payloads


def pool_specs(axis: str) -> SubtreePool:
    """PartitionSpecs of the pool's planes: the top tree replicated, each
    block plane sharded on its subtree axis over ``axis``."""
    return SubtreePool(
        top_keys=P(),
        top_children=P(),
        pool_keys=Words(P(axis), P(axis)),
        pool_children=P(axis),
        pool_values=Words(P(axis), P(axis)),
    )


@dataclasses.dataclass(frozen=True)
class PoolMeta:
    level_m: int              # subtree root level (0 = leaves only)
    per_node: int             # fill-factor entries per node at build
    subtree_cap: int          # nodes per subtree block (incl. headroom)
    n_subtrees: int           # real subtrees (<= padded S)
    n_subtrees_padded: int
    top_height: int           # levels above M (0 => single-subtree tree)
    n_keys: int
    leaf_start: int           # local id of first leaf within a block
    base_cap: int = 0         # nodes per block used by the bulk layout;
    #                           [base_cap, subtree_cap) is SMO headroom
    subtree_leaves: int = 0   # leaves per block at build (0 = the dense
    #                           default per_node**level_m); smaller blocks
    #                           leave block roots separator room for splits

    @property
    def leaves_per_subtree(self) -> int:
        return self.subtree_leaves or self.per_node**self.level_m

    @property
    def levels_in_subtree(self) -> int:
        return self.level_m + 1

    @property
    def min_leaf_fill(self) -> int:
        """Smallest key count a *non-last* leaf can hold: bulk-built leaves
        carry ``per_node`` keys and an on-mesh split leaves each half with at
        least ``FANOUT // 2`` (core/smo.py splits only overflowing rows)."""
        return min(self.per_node, FANOUT // 2)

    @property
    def headroom_frac(self) -> float:
        """Free-list fraction this pool was built with (for rebuilds)."""
        if self.base_cap <= 0:
            return 0.0
        return (self.subtree_cap - self.base_cap) / self.base_cap

    def node_gid(self, subtree: jax.Array, local: jax.Array) -> jax.Array:
        """Global node id used as the cache tag."""
        return subtree.astype(jnp.int64) * self.subtree_cap + local


def _level_offsets(
    per_node: int, level_m: int, subtree_leaves: "int | None" = None
) -> np.ndarray:
    """Local-id offset of each subtree level: level M at 0, leaves last.

    ``subtree_leaves`` overrides the dense default of ``per_node**level_m``
    leaves per block — fewer leaves per subtree build the block's root with
    fewer children, leaving separator room for on-mesh splits (and spread a
    dataset over more subtrees / memory columns).
    """
    if subtree_leaves is None:
        subtree_leaves = per_node**level_m
    counts = [subtree_leaves]                      # level 0 (leaves) first
    for _ in range(level_m):
        counts.append(-(-counts[-1] // per_node))
    counts[-1] = 1                                 # block root
    sizes = counts[::-1]                           # level M..0 counts
    return np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)


DEFAULT_HEADROOM = 0.5


def build_pool(keys: np.ndarray, values: Optional[np.ndarray] = None,
               **kw) -> Tuple[SubtreePool, PoolMeta]:
    """:func:`build_pool_host` with the pool's planes on the default
    device."""
    pool, meta = build_pool_host(keys, values, **kw)
    return jax.tree.map(jnp.asarray, pool), meta


def build_pool_host(
    keys: np.ndarray,
    values: Optional[np.ndarray] = None,
    *,
    level_m: int = 1,
    fill: float = 0.7,
    n_shards: int = 1,
    headroom: float = DEFAULT_HEADROOM,
    subtree_leaves: Optional[int] = None,
) -> Tuple[SubtreePool, PoolMeta]:
    """Bulk-build the blocked pool from sorted unique keys, as host numpy
    planes (``jax.device_put`` them under a sharding to place each shard
    straight on its device).

    ``n_shards``: pad the subtree axis to a multiple of this (the `model`
    mesh axis size) so the arrays block-shard evenly.  ``headroom``: extra
    node slots per subtree block, as a fraction of the bulk layout's node
    count — the free-list the on-mesh SMO engine allocates split siblings
    from (0 disables device-side splits; every overflow then drains through
    the host rebuild).  ``subtree_leaves``: leaves per block (default the
    dense ``per_node**level_m``); smaller blocks build roomier block roots
    (more separator slack before a subtree overflows to the host path) and
    spread a dataset over more subtrees.
    """
    keys = np.asarray(keys, dtype=np.int64)
    if np.any(keys[1:] <= keys[:-1]):
        raise ValueError("keys must be sorted and unique")
    if values is None:
        values = keys.copy()
    values = np.asarray(values, dtype=np.int64)
    if headroom < 0:
        raise ValueError(f"headroom must be >= 0, got {headroom!r}")

    per_node = max(2, int(FANOUT * fill))
    n = keys.size
    n_leaves = -(-n // per_node)
    if subtree_leaves is None:
        subtree_leaves = per_node**level_m
    if not (1 <= subtree_leaves <= per_node**level_m):
        raise ValueError(
            f"subtree_leaves must be in [1, per_node**level_m], got "
            f"{subtree_leaves!r}"
        )
    leaves_per_subtree = int(subtree_leaves)
    n_subtrees = -(-n_leaves // leaves_per_subtree)
    S = -(-n_subtrees // n_shards) * n_shards
    offs = _level_offsets(per_node, level_m, leaves_per_subtree)
    base_cap = int(offs[-1])
    cap = base_cap + int(np.ceil(base_cap * headroom))
    leaf_start = int(offs[-2])

    # the key and value planes are built as word planes in place: no int64
    # copy of the whole pool is ever held
    PK = np.empty((2, S, cap, FANOUT), dtype=np.int32)
    PK[0], PK[1] = split_words(KEY_MAX, np)
    PC = np.full((S, cap, FANOUT), NULL, dtype=np.int32)
    PV = np.zeros((2, S, cap, FANOUT), dtype=np.int32)

    # pad keys to full leaves for reshaping
    pad = (-n) % per_node
    kp = np.concatenate([keys, np.full((pad,), KEY_MAX, np.int64)])
    vp = np.concatenate([values, np.zeros((pad,), np.int64)])
    leaf_k = kp.reshape(n_leaves, per_node)
    leaf_v = vp.reshape(n_leaves, per_node)

    # place the leaves of every full block at once, then the last block's
    n_full = n_leaves // leaves_per_subtree
    full = n_full * leaves_per_subtree
    leaves = np.s_[:n_full, leaf_start : leaf_start + leaves_per_subtree,
                   :per_node]
    shape = (n_full, leaves_per_subtree, per_node)
    _put_words(PK, leaves, leaf_k[:full].reshape(shape))
    _put_words(PV, leaves, leaf_v[:full].reshape(shape))
    if full < n_leaves:
        tail = np.s_[n_full, leaf_start : leaf_start + n_leaves - full,
                     :per_node]
        _put_words(PK, tail, leaf_k[full:])
        _put_words(PV, tail, leaf_v[full:])

    subtree_mins = np.full((S,), KEY_MAX, dtype=np.int64)

    for s in range(n_subtrees):
        lk = leaf_k[s * leaves_per_subtree : (s + 1) * leaves_per_subtree]
        nl = lk.shape[0]
        # routing minima for this subtree's leaves
        mins = lk[:, 0].copy()
        child_ids = np.arange(leaf_start, leaf_start + nl, dtype=np.int32)
        # build levels 1..M bottom-up
        for lvl in range(1, level_m + 1):
            lvl_off = int(offs[level_m - lvl])
            n_nodes = -(-child_ids.size // per_node)
            new_mins = np.empty((n_nodes,), np.int64)
            for i in range(n_nodes):
                cm = mins[i * per_node : (i + 1) * per_node]
                ch = child_ids[i * per_node : (i + 1) * per_node]
                nid = lvl_off + i
                _put_words(PK, np.s_[s, nid, : cm.size], cm)
                PC[s, nid, : ch.size] = ch
                new_mins[i] = cm[0]
            mins = new_mins
            child_ids = np.arange(lvl_off, lvl_off + n_nodes, dtype=np.int32)
        # note: no -inf sentinel is needed inside blocks — the in-node search
        # clamps slot 0, so queries below a block's min route leftmost anyway
        subtree_mins[s] = lk[0, 0] if s > 0 else KEY_MIN

    # ---- top tree over subtree minima --------------------------------------
    top_k_rows = []
    top_c_rows = []
    child_refs = np.arange(n_subtrees, dtype=np.int32)  # subtree ids
    mins = subtree_mins[:n_subtrees].copy()
    top_height = 0
    while child_refs.size > 1 or top_height == 0:
        n_nodes = -(-child_refs.size // per_node)
        if child_refs.size == 1 and top_height > 0:
            break
        new_refs = np.empty((n_nodes,), np.int32)
        new_mins = np.empty((n_nodes,), np.int64)
        for i in range(n_nodes):
            cm = mins[i * per_node : (i + 1) * per_node]
            ch = child_refs[i * per_node : (i + 1) * per_node]
            row_k = np.full((FANOUT,), KEY_MAX, np.int64)
            row_c = np.full((FANOUT,), NULL, np.int32)
            row_k[: cm.size] = cm
            row_c[: ch.size] = ch
            top_k_rows.append(row_k)
            top_c_rows.append(row_c)
            new_refs[i] = len(top_k_rows) - 1
            new_mins[i] = cm[0]
        child_refs, mins = new_refs, new_mins
        top_height += 1
        if n_nodes == 1:
            break

    TK = np.stack(top_k_rows) if top_k_rows else np.full((1, FANOUT), KEY_MAX, np.int64)
    TC = np.stack(top_c_rows) if top_c_rows else np.full((1, FANOUT), NULL, np.int32)

    pool = SubtreePool(
        top_keys=TK,
        top_children=TC,
        pool_keys=Words(*PK.reshape(2, S, cap * FANOUT)),
        pool_children=PC.reshape(S, cap * FANOUT),
        pool_values=Words(*PV.reshape(2, S, cap * FANOUT)),
    )
    meta = PoolMeta(
        level_m=level_m,
        per_node=per_node,
        subtree_cap=cap,
        n_subtrees=n_subtrees,
        n_subtrees_padded=S,
        top_height=top_height,
        n_keys=n,
        leaf_start=leaf_start,
        base_cap=base_cap,
        subtree_leaves=leaves_per_subtree,
    )
    return pool, meta


def initial_succ(meta: PoolMeta) -> np.ndarray:
    """Leaf successor table over the bulk layout: ``succ[gid]`` is the next
    leaf's global node id in key order (``-1`` ends the chain; non-leaf
    slots are ``-1``).  On-mesh leaf splits (core/smo.py) link allocated
    siblings into this chain; range scans (core/scan.py) follow it instead
    of assuming leaves are consecutive in local-id order."""
    n_nodes = meta.n_subtrees_padded * meta.subtree_cap
    succ = np.full((n_nodes,), -1, dtype=np.int64)
    n_leaves = -(-meta.n_keys // meta.per_node)
    lps = meta.leaves_per_subtree
    g = np.arange(n_leaves, dtype=np.int64)
    gid = (g // lps) * meta.subtree_cap + meta.leaf_start + (g % lps)
    succ[gid[:-1]] = gid[1:]
    return succ


# ---------------------------------------------------------------------------
# Prefix-compressed separators (DESIGN.md §13)
# ---------------------------------------------------------------------------

# Suffixes keep at most 30 low bits so they fit a non-negative int32 lane
# with room for an unambiguous padding sentinel above every real value.
SEP_MAX_NBITS = 30
SEP_SUFFIX_SENTINEL = np.int32(0x7FFFFFFF)


class SepPlanes(NamedTuple):
    """Prefix-compressed separator planes for the pool's node rows.

    Within one node the separators share their high bits (a row spans a
    narrow key range), so each row stores one 8-byte common ``prefix`` (low
    ``nbits`` zeroed), the retained low-bit count ``nbits``, and FANOUT
    4-byte truncated suffixes — 8 + 4 + 4*FANOUT bytes against the
    canonical 8*FANOUT, i.e. roughly twice the separators per byte of
    fetched row.  ``nbits = -1`` marks an incompressible row (its span
    needs more than SEP_MAX_NBITS low bits — e.g. a block root over a
    sparse keyspace); searches fall back to the full key row there
    (kernels/node_search.py ``node_search_prefix``).  Padding suffix slots
    hold SEP_SUFFIX_SENTINEL, which is greater than any real (< 2**30)
    suffix, so a row's real separator count is recoverable from the plane
    alone."""

    prefix: jax.Array   # [S, C] int64 shared high bits (low nbits zeroed)
    nbits: jax.Array    # [S, C] int32 retained low bits; -1 = incompressible
    suffix: jax.Array   # [S, C, FANOUT] int32 truncated separators


def _bit_length(x: np.ndarray) -> np.ndarray:
    """Per-element ``int.bit_length`` of int64 bit patterns read as
    unsigned (a span crossing the sign bit must count all 64 bits)."""
    bl = np.frompyfunc(lambda v: int(v).bit_length(), 1, 1)
    return bl(x.astype(np.uint64).astype(object)).astype(np.int32)


def compress_rows(keys: np.ndarray):
    """Compress [N, FANOUT] separator rows (KEY_MAX padding) into
    ``(prefix [N], nbits [N], suffix [N, FANOUT])`` numpy planes.

    A row's retained-bit count is the bit length of ``min ^ max`` over its
    real keys: every key in between shares the bits above that, so the
    query-side comparison reduces to one prefix compare plus an int32
    suffix compare (``node_search_prefix_ref`` spells out the contract).
    Empty rows compress trivially (all-sentinel suffixes, count 0)."""
    keys = np.asarray(keys, np.int64)
    n, f = keys.shape
    real = keys != KEY_MAX
    any_real = real.any(axis=1)
    lo = np.where(any_real, np.min(np.where(real, keys, KEY_MAX), axis=1), 0)
    hi = np.where(any_real, np.max(np.where(real, keys, KEY_MIN), axis=1), 0)
    # xor of the row extremes: the keys differ only below its bit length
    nbits = _bit_length(lo ^ hi)
    good = any_real & (nbits <= SEP_MAX_NBITS)
    nbits = np.where(any_real, np.where(good, nbits, -1), 0).astype(np.int32)
    mask = np.where(good, (np.int64(1) << np.maximum(nbits, 0)) - 1, 0)
    prefix = np.where(good, lo & ~mask, 0)
    suffix = np.where(
        real & good[:, None],
        (keys & mask[:, None]).astype(np.int64),
        np.int64(SEP_SUFFIX_SENTINEL),
    ).astype(np.int32)
    return prefix, nbits, suffix


def compress_separators(pool: SubtreePool, meta: PoolMeta) -> SepPlanes:
    """Build the compressed separator planes for every pool row at load
    (host-side; core/smo.py ``refresh_sep_planes`` keeps them correct
    across on-mesh splits without a full rebuild)."""
    pk = blocks(keys_i64(pool))
    s, c, f = pk.shape
    prefix, nbits, suffix = compress_rows(pk.reshape(s * c, f))
    return SepPlanes(
        prefix=jnp.asarray(prefix.reshape(s, c)),
        nbits=jnp.asarray(nbits.reshape(s, c)),
        suffix=jnp.asarray(suffix.reshape(s, c, f)),
    )


def sep_compression_stats(sep: SepPlanes, meta: PoolMeta) -> dict:
    """Byte/fanout accounting for the compressed layout (fig16/fig20).

    ``effective_fanout`` is how many separators a canonical row's byte
    budget (8*FANOUT) holds under the compressed layout's per-row cost
    (8 + 4 + 4*FANOUT amortized per separator), i.e. the fanout a fetch of
    the same size could route over; ``modeled_depth`` is the within-subtree
    descent depth that fanout would need for the same leaf population."""
    nbits = np.asarray(sep.nbits).reshape(-1)
    suffix = np.asarray(sep.suffix)
    counts = (suffix != SEP_SUFFIX_SENTINEL).sum(axis=-1).reshape(-1)
    occupied = counts > 0
    n_rows = int(occupied.sum())
    compressible = int((occupied & (nbits >= 0)).sum())
    f = suffix.shape[-1]
    canon_bytes = 8 * f
    comp_bytes = 8 + 4 + 4 * f
    eff_fanout = f * canon_bytes / comp_bytes
    leaves = max(meta.leaves_per_subtree, 1)
    modeled_depth = int(np.ceil(np.log(max(leaves, 2)) / np.log(eff_fanout)))
    return {
        "rows": n_rows,
        "compressible_rows": compressible,
        "compressible_frac": compressible / max(n_rows, 1),
        "mean_nbits": float(nbits[occupied & (nbits >= 0)].mean())
        if compressible
        else 0.0,
        "canonical_row_bytes": canon_bytes,
        "compressed_row_bytes": comp_bytes,
        "effective_fanout": eff_fanout,
        "modeled_subtree_depth": modeled_depth,
        "baseline_subtree_depth": meta.level_m,
    }


# ---------------------------------------------------------------------------
# Block-row access (see the module docstring)
# ---------------------------------------------------------------------------


def split_words(x, xp=jnp) -> Words:
    """int64 ``x`` -> its int32 :class:`Words`: the high word ``x >> 32``
    and the low 32 bits reinterpreted as int32 (``xp`` is ``np`` on the
    host, ``jnp`` in a program)."""
    x = xp.asarray(x).astype(xp.int64)
    hi = (x >> 32).astype(xp.int32)
    lo = (x & 0xFFFFFFFF).astype(xp.uint32).astype(xp.int32)
    return Words(hi, lo)


def join_words(words, xp=jnp):
    """A ``(hi, lo)`` pair of int32 words -> the int64 values (inverse of
    :func:`split_words`)."""
    hi, lo = words
    return (hi.astype(xp.int64) << 32) | lo.astype(xp.uint32).astype(xp.int64)


def _put_words(plane, index, x) -> None:
    """Host build: write int64 ``x`` into the numpy word planes ``plane``
    (``[2, ...]``) at ``plane[:, *index]`` (numpy's int64 -> int32
    assignment keeps the low 32 bits)."""
    plane[0][index] = x >> 32
    plane[1][index] = x


def keys_i64(pool: SubtreePool, xp=np):
    """The pool's keys as one int64 ``[S, C * FANOUT]`` plane (``np``: a
    host copy; ``jnp``: an array on the planes' devices)."""
    return join_words([xp.asarray(w) for w in pool.pool_keys], xp)


def values_i64(pool: SubtreePool, xp=np):
    """The pool's values as one int64 ``[S, C * FANOUT]`` plane, as
    :func:`keys_i64`."""
    return join_words([xp.asarray(w) for w in pool.pool_values], xp)


def blocks(plane):
    """``[S, C * FANOUT]`` block rows -> the ``[S, C, FANOUT]`` node view
    (numpy or jax; on a device this is a relayout — host code and one-off
    passes only)."""
    return plane.reshape(plane.shape[0], -1, FANOUT)


def _row_index(plane, subtree, local):
    """(block row, first lane) start indices with numpy index semantics:
    negative ids wrap; the gather clamps, the scatter drops, the rest."""
    s, cf = plane.shape
    c = cf // FANOUT
    st = jnp.asarray(subtree).astype(jnp.int32)
    lo = jnp.asarray(local).astype(jnp.int32)
    st = jnp.where(st < 0, st + s, st)
    lo = jnp.where(lo < 0, lo + c, lo)
    st, lo = jnp.broadcast_arrays(st, lo)
    return jnp.stack([st, lo * FANOUT], axis=-1)


def node_rows(plane, subtree, local) -> jax.Array:
    """Rows ``[..., FANOUT]`` of nodes ``(subtree, local)`` of a block-row
    plane — ``blocks(plane)[subtree, local]`` without a relayout.  Of a
    :class:`Words` plane both words of a row are gathered with the one
    index and the rows come back joined to int64."""
    if isinstance(plane, Words):
        return join_words([node_rows(w, subtree, local) for w in plane])
    idx = _row_index(plane, subtree, local)
    dn = jax.lax.GatherDimensionNumbers(
        offset_dims=(idx.ndim - 1,), collapsed_slice_dims=(0,),
        start_index_map=(0, 1),
    )
    return jax.lax.gather(plane, idx, dn, slice_sizes=(1, FANOUT),
                          mode="clip")


def set_node_rows(plane, subtree, local, rows):
    """``plane`` with ``rows [..., FANOUT]`` written at nodes ``(subtree,
    local)``; nodes outside the plane are dropped, the same contract as
    ``.at[subtree, local].set(rows, mode="drop")`` on the node view.  Rows
    bound for a :class:`Words` plane are split into their words first."""
    if isinstance(plane, Words):
        return Words(*(set_node_rows(w, subtree, local, r)
                       for w, r in zip(plane, split_words(rows))))
    idx = _row_index(plane, subtree, local)
    dn = jax.lax.ScatterDimensionNumbers(
        update_window_dims=(idx.ndim - 1,), inserted_window_dims=(0,),
        scatter_dims_to_operand_dims=(0, 1),
    )
    return jax.lax.scatter(plane, idx, rows.astype(plane.dtype), dn,
                           mode="drop")


# ---------------------------------------------------------------------------
# Pure-jnp traversal pieces (shared by Plane B and by kernel oracles)
# ---------------------------------------------------------------------------


def _slot(node_keys: jax.Array, q: jax.Array) -> jax.Array:
    cnt = jnp.sum(node_keys <= q[..., None], axis=-1)
    return jnp.maximum(cnt - 1, 0).astype(jnp.int32)


def top_walk(pool: SubtreePool, meta: PoolMeta, queries: jax.Array) -> jax.Array:
    """Walk the replicated top tree; returns the subtree id per query."""
    queries = queries.astype(jnp.int64)
    b = queries.shape[0]
    if meta.top_height == 0:
        return jnp.zeros((b,), jnp.int32)
    root = pool.top_keys.shape[0] - 1
    nodes = jnp.full((b,), root, jnp.int32)
    for _ in range(meta.top_height - 1):
        s = _slot(pool.top_keys[nodes], queries)
        nodes = pool.top_children[nodes, s]
    s = _slot(pool.top_keys[nodes], queries)
    return pool.top_children[nodes, s]  # subtree ids


def subtree_walk_ref(
    block_keys: jax.Array,      # [C, FANOUT] (or a [C * FANOUT] block row)
    block_children: jax.Array,  # [C, FANOUT]
    block_values: jax.Array,    # [C, FANOUT]
    queries: jax.Array,         # [B]
    *,
    levels: int,
) -> Tuple[jax.Array, jax.Array]:
    """Walk one subtree block from its root (local id 0) to the leaves.
    Pure-jnp oracle for the Pallas ``subtree_walk`` kernel; also the
    offload executor's reference implementation.  Returns (found, values).
    """
    block_keys, block_children, block_values = (
        x.reshape(-1, FANOUT) for x in (block_keys, block_children,
                                        block_values)
    )
    queries = queries.astype(jnp.int64)
    b = queries.shape[0]
    local = jnp.zeros((b,), jnp.int32)
    for _ in range(levels - 1):
        s = _slot(block_keys[local], queries)
        local = block_children[local, s]
    leaf_keys = block_keys[local]
    eq = leaf_keys == queries[..., None]
    found = jnp.any(eq, axis=-1)
    vals = jnp.sum(jnp.where(eq, block_values[local], 0), axis=-1)
    return found, vals


def pool_lookup_ref(
    pool: SubtreePool, meta: PoolMeta, queries: jax.Array
) -> Tuple[jax.Array, jax.Array]:
    """Single-device reference lookup over the blocked layout (no mesh)."""
    st = top_walk(pool, meta, queries)
    queries = queries.astype(jnp.int64)
    b = queries.shape[0]
    local = jnp.zeros((b,), jnp.int32)
    for _ in range(meta.levels_in_subtree - 1):
        rows = node_rows(pool.pool_keys, st, local)
        s = _slot(rows, queries)
        local = pool.pool_children[st, local * FANOUT + s]
    leaf_keys = node_rows(pool.pool_keys, st, local)
    eq = leaf_keys == queries[..., None]
    found = jnp.any(eq, axis=-1)
    vals = jnp.sum(
        jnp.where(eq, node_rows(pool.pool_values, st, local), 0), axis=-1
    )
    return found, vals
