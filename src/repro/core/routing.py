"""Shared request-routing layer for the mesh plane (Plane B).

Every sharded DEX operation — point lookup (core/dex.py) and range scan
(core/scan.py) — moves work between chips the same way:

  1. bucket a batch of requests by destination with bounded capacity
     (:func:`pack_by_dest`), the SPMD analogue of per-server send queues;
  2. exchange the buckets with ``all_to_all`` collectives, composing two
     exchanges when the compute partitions span two mesh axes
     (:func:`route_exchange`);
  3. serve, then exchange back and scatter responses to the originating
     lanes (:func:`unpack_to_lanes`).

:func:`fetch_rows` layers the RDMA-READ analogue on top of (1)–(3): a
request/response ``all_to_all`` over the memory axis carrying 1KB node rows,
one round per tree level (DESIGN.md §2).

All helpers are intended to run *inside* ``shard_map``; ``cfg`` is any object
with the :class:`repro.core.dex.DexMeshConfig` routing attributes
(``route_axes``, ``memory_axis``, ``n_memory``, ``route_capacity_factor``) —
duck-typed to keep this module import-light.
"""

from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import shard_map_compat  # noqa: F401  (re-export)
from repro.core.nodes import KEY_MAX
from repro.core.pool import PoolMeta, SubtreePool, node_rows


def hash64(x: jax.Array) -> jax.Array:
    """SplitMix64 finalizer; used for cache set indexing and admission dice."""
    x = x.astype(jnp.uint64)
    x = (x ^ (x >> jnp.uint64(33))) * jnp.uint64(0xFF51AFD7ED558CCD)
    x = (x ^ (x >> jnp.uint64(33))) * jnp.uint64(0xC4CEB9FE1A85EC53)
    return x ^ (x >> jnp.uint64(33))


def leaf_admit_dice(gid: jax.Array, pct, salt=None) -> jax.Array:
    """Lazy leaf-admission coin flip (paper §5.4, P_A), deterministic per
    (node id, ``salt``).  Ops pass their chip's running op counter plus the
    lane index as the salt, so every *access* re-rolls the dice — a hot
    leaf that loses the flip can still be admitted on a later access, the
    same per-miss coin-flip semantics the paper (and the Plane-A simulator)
    uses, just derived from a hash instead of an RNG stream."""
    x = gid ^ jnp.int64(0x9E3779B9)
    if salt is not None:
        x = x ^ (jnp.int64(salt) * jnp.int64(0x5851F42D4C957F2D))
    luck = (hash64(x) % jnp.uint64(100)).astype(jnp.int32)
    return luck < pct


def rt_predict(rt_keys: jax.Array, rt_sub: jax.Array, rt_local: jax.Array,
               keys: jax.Array):
    """Leaf-direct route-table segment lookup (DESIGN.md §13).

    ``rt_keys`` is the sorted fence-low plane of the trained table — a
    piecewise-linear index over the observed key hull whose segment lookup
    is one ``searchsorted`` against replicated arrays (compute-side; no
    collective, no remote read).  Returns ``(idx, pred_subtree,
    pred_local)`` — the *guess*; the engine only acts on it after
    :func:`repro.core.fleet_cache.rt_accept` verifies the fence-key bounds
    and the leaf version fence, so a wrong guess costs one rejected
    prediction, never a wrong answer."""
    r = rt_keys.shape[0]
    idx = jnp.clip(
        jnp.searchsorted(rt_keys, keys, side="right") - 1, 0, r - 1
    ).astype(jnp.int32)
    return idx, rt_sub[idx].astype(jnp.int32), rt_local[idx].astype(jnp.int32)


def route_capacity(b: int, n_dest: int, factor: float) -> int:
    """Per-destination bucket capacity for a batch of ``b`` requests."""
    return int(np.ceil(b / n_dest * factor))


def route_owners(boundaries: jax.Array, keys: jax.Array, n_route: int):
    """Owning compute partition per lane (logical partitioning, §4), plus
    this batch's per-partition demand.

    Returns ``(owner [B] int32, demand [1, n_route] int64)``.  ``demand``
    counts every real lane *before* bucketing — shed lanes included — so it
    never saturates at bucket capacity the way served-op counters do (it
    accumulates into ``DexState.route_demand``, the repartition
    controller's load signal).  Inactive lanes (``KEY_MAX``, masked mixed
    batches) get the out-of-bounds sentinel destination ``n_route`` — they
    scatter nowhere in :func:`pack_by_dest` (``mode="drop"``), consume no
    bucket capacity and contribute no demand; callers must mask the
    returned ``dropped`` flags with their real-lane mask, since overflow of
    the sentinel run is meaningless (same contract as the offload path)."""
    owner = (
        jnp.searchsorted(boundaries, keys, side="right") - 1
    ).astype(jnp.int32)
    owner = jnp.clip(owner, 0, n_route - 1)
    demand = jnp.zeros((1, n_route), jnp.int64).at[0, owner].add(
        (keys != KEY_MAX).astype(jnp.int64)
    )
    owner = jnp.where(keys == KEY_MAX, n_route, owner)
    return owner, demand


def pack_by_dest(payload: jax.Array, dest: jax.Array, n_dest: int, cap: int):
    """Bucket ``payload`` rows by destination with bounded capacity.

    Returns ``(buf, lane_of_slot, dropped)``:
      * ``buf``: [n_dest, cap, ...] payload (KEY_MAX padding)
      * ``lane_of_slot``: [n_dest, cap] originating lane (B = OOB sentinel)
      * ``dropped``: [B] lanes that exceeded a bucket's capacity (these are
        load-shed, mirrored by a stats counter — the caller retries or
        reports; logical repartitioning is the systemic fix, §4)
    """
    b = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sd = dest[order]
    new = jnp.concatenate([jnp.ones((1,), bool), sd[1:] != sd[:-1]])
    start = jax.lax.cummax(jnp.where(new, jnp.arange(b), 0), axis=0)
    rank = jnp.arange(b) - start
    ok = rank < cap
    pad_shape = (n_dest, cap) + payload.shape[1:]
    fill = KEY_MAX if payload.dtype == jnp.int64 else 0
    buf = jnp.full(pad_shape, fill, payload.dtype)
    buf = buf.at[sd, rank].set(payload[order], mode="drop")
    lane = jnp.full((n_dest, cap), b, jnp.int32)
    lane = lane.at[sd, rank].set(order.astype(jnp.int32), mode="drop")
    dropped = jnp.zeros((b,), bool).at[order].set(~ok)
    return buf, lane, dropped


def unpack_to_lanes(resp: jax.Array, lane_of_slot: jax.Array, b: int, fill):
    """Scatter [n_dest, cap, ...] responses back to [B, ...] lanes."""
    flat_lane = lane_of_slot.reshape(-1)
    flat = resp.reshape((-1,) + resp.shape[2:])
    out = jnp.full((b,) + resp.shape[2:], fill, resp.dtype)
    return out.at[flat_lane].set(flat, mode="drop")


# trace-time collective bookkeeping: every ``all_to_all`` issued while
# tracing a mesh program bumps these, so a benchmark can count the
# collective rounds of a jitted op without parsing HLO
# (:func:`trace_collective_counts`).  When a :func:`trace_phase` label is
# active the bump is also attributed to that phase — the pipelined engine
# labels its two stages so benchmarks can assert WHICH half of the step
# carries each collective (the overlap story: the fused write round rides
# in the back half, hidden under the next batch's descent).
_TRACE_COUNTS = {"all_to_all": 0, "route_exchange": 0}
_TRACE_PHASE: list = [None]
_TRACE_BY_PHASE: dict = {}


@contextlib.contextmanager
def trace_phase(label: str):
    """Attribute collectives issued inside this block to ``label`` during
    abstract tracing (metadata only — adds nothing to the program)."""
    prev = _TRACE_PHASE[0]
    _TRACE_PHASE[0] = label
    try:
        yield
    finally:
        _TRACE_PHASE[0] = prev


def _count_collective(kind: str) -> None:
    _TRACE_COUNTS[kind] += 1
    label = _TRACE_PHASE[0]
    if label is not None:
        per = _TRACE_BY_PHASE.setdefault(
            label, {"all_to_all": 0, "route_exchange": 0}
        )
        per[kind] += 1


def trace_collective_counts(fn, *args, by_phase: bool = False, **kwargs):
    """Abstractly trace ``fn(*args, **kwargs)`` and return how many
    ``all_to_all`` collectives and ``route_exchange`` invocations the traced
    program contains — the honest "communication rounds per batch" metric
    the engine benchmark asserts on (benchmarks/fig13_mesh_engine.py).

    With ``by_phase=True`` the result gains a ``"phases"`` entry splitting
    the counts by the :func:`trace_phase` labels active when each collective
    was issued (the pipelined engine labels ``pipe/front``/``pipe/back``)."""
    before = dict(_TRACE_COUNTS)
    before_phase = {k: dict(v) for k, v in _TRACE_BY_PHASE.items()}
    jax.eval_shape(fn, *args, **kwargs)
    out = {k: _TRACE_COUNTS[k] - before[k] for k in _TRACE_COUNTS}
    if by_phase:
        phases = {}
        for label, per in _TRACE_BY_PHASE.items():
            prev = before_phase.get(label, {})
            diff = {k: per[k] - prev.get(k, 0) for k in per}
            if any(diff.values()):
                phases[label] = diff
        out["phases"] = phases
    return out


def a2a(x: jax.Array, axis: str) -> jax.Array:
    """[n_axis, ...] per-destination buffers -> per-source buffers."""
    _count_collective("all_to_all")
    return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)


def route_exchange(buf: jax.Array, cfg, mesh, *, reverse: bool = False) -> jax.Array:
    """Exchange per-destination buckets across the compute-partition axes.

    ``buf`` is [n_route, cap, ...].  With one route axis this is a single
    ``all_to_all``; with two, the exchanges over each axis compose to the full
    permutation (and must be applied in the opposite order on the way back,
    ``reverse=True``).
    """
    _count_collective("route_exchange")
    if len(cfg.route_axes) == 1:
        return a2a(buf, cfg.route_axes[0])
    a0, a1 = cfg.route_axes
    s1 = mesh.shape[a1]
    r = buf.reshape((buf.shape[0] // s1, s1) + buf.shape[1:])

    def x0(r):
        _count_collective("all_to_all")
        return jax.lax.all_to_all(r, a0, split_axis=0, concat_axis=0)

    def x1(r):
        _count_collective("all_to_all")
        r = jnp.swapaxes(r, 0, 1)
        r = jax.lax.all_to_all(r, a1, split_axis=0, concat_axis=0)
        return jnp.swapaxes(r, 0, 1)

    r = x0(x1(r)) if reverse else x1(x0(r))
    return r.reshape(buf.shape)


def device_linear_index(cfg, mesh) -> jax.Array:
    """This device's linear position over *all* mesh axes (route-major),
    matching how ``P(cfg.all_axes)``-sharded batch dims are chunked.  Used to
    derive globally unique per-lane priorities for write conflict
    resolution."""
    idx = jnp.int32(0)
    for ax in cfg.all_axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def route_linear_index(cfg, mesh) -> jax.Array:
    """This device's linear position along the composed route axes (matches
    the leading axis of :func:`gather_route`)."""
    idx = jnp.int32(0)
    for ax in cfg.route_axes:
        idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
    return idx


def gather_route(x: jax.Array, cfg) -> jax.Array:
    """All-gather ``x`` across the route axes: ``[...] -> [n_route, ...]``.

    The write path uses this to make every route-replica of a memory
    column's pool shard apply the *same* batch of writes: the pool is only
    sharded over the memory axis, so devices along the route axes hold
    replicas that must mutate identically (the SPMD analogue of "the memory
    server applies the write once, all compute servers see it")."""
    shape = x.shape
    for ax in reversed(cfg.route_axes):
        x = jax.lax.all_gather(x, ax, axis=0)
    return x.reshape((cfg.n_route,) + shape)


def fetch_rows(
    pool: SubtreePool,
    meta: PoolMeta,
    cfg,
    gid: jax.Array,
    want: jax.Array,
):
    """Remote-read node rows (the RDMA READ analogue): request/response
    all_to_all over the memory axis.  Lanes with ``want == False`` send a
    padded no-op request.

    Requests are *coalesced*: duplicate gids on this chip (a hot node
    missed by many lanes of one batch) collapse into a single remote read
    whose response fans back out to every requesting lane — fewer messages
    and far less routing-bucket pressure under zipfian skew.  Returns
    ``(keys, children, values, dropped, n_msgs)`` where ``n_msgs`` is the
    number of coalesced read messages actually served (the RDMA-READ count
    for stats)."""
    b = gid.shape[0]
    gidr = jnp.where(want, gid, KEY_MAX)
    order = jnp.argsort(gidr, stable=True)
    gs = gidr[order]
    head = jnp.concatenate([jnp.ones((1,), bool), gs[1:] != gs[:-1]])
    rep_sorted = jax.lax.cummax(
        jnp.where(head, jnp.arange(b), 0), axis=0
    )                                         # sorted-pos of my run's head
    rep = (
        jnp.zeros((b,), jnp.int32)
        .at[order].set(order[rep_sorted].astype(jnp.int32))
    )                                         # lane -> representative lane
    is_head = jnp.zeros((b,), bool).at[order].set(head)
    want_h = want & is_head                   # only representatives send

    s_per = meta.n_subtrees_padded // cfg.n_memory
    subtree = (gid // meta.subtree_cap).astype(jnp.int32)
    owner = jnp.where(want_h, subtree // s_per, cfg.n_memory)  # OOB if unused
    cap = route_capacity(b, cfg.n_memory, cfg.route_capacity_factor)
    buf, lane, dropped = pack_by_dest(gid, owner.astype(jnp.int32), cfg.n_memory, cap)
    req = a2a(buf, cfg.memory_axis)                        # [n_mem, cap]
    # serve locally: decode gid -> (local subtree, local node)
    st = (req // meta.subtree_cap).astype(jnp.int32) % s_per
    lo = (req % meta.subtree_cap).astype(jnp.int32)
    valid = req != KEY_MAX
    st = jnp.where(valid, st, 0)
    lo = jnp.where(valid, lo, 0)
    rk = node_rows(pool.pool_keys, st, lo)                 # [n_mem, cap, F]
    rc = node_rows(pool.pool_children, st, lo)
    rv = node_rows(pool.pool_values, st, lo)
    rk = jnp.where(valid[..., None], rk, KEY_MAX)
    rc = jnp.where(valid[..., None], rc, 0)
    rv = jnp.where(valid[..., None], rv, 0)
    rk = a2a(rk, cfg.memory_axis)
    rc = a2a(rc, cfg.memory_axis)
    rv = a2a(rv, cfg.memory_axis)
    out_k = unpack_to_lanes(rk, lane, b, KEY_MAX)
    out_c = unpack_to_lanes(rc, lane, b, 0)
    out_v = unpack_to_lanes(rv, lane, b, 0)
    # fan the representative's response (and shed fate) out to duplicates;
    # only lanes that actually wanted a fetch can be load-shed: no-op lanes
    # share the OOB sentinel bucket, whose overflow is meaningless
    shed = dropped[rep] & want
    n_msgs = jnp.sum(want_h & ~dropped).astype(jnp.int64)
    return out_k[rep], out_c[rep], out_v[rep], shed, n_msgs
