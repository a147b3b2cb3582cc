"""DEX on a TPU mesh (Plane B): logical partitioning, per-chip caching and
opportunistic offloading expressed as SPMD collectives.

Mapping (DESIGN.md §2):

  compute server   -> a chip; key ranges are owned by rows of the
                      ``route`` axes (logical partitioning)
  memory server    -> a column of the ``memory`` axis; the subtree-blocked
                      pool (core/pool.py) block-shards over it, so a whole
                      level-M subtree lives on one column (paper §3)
  RDMA READ        -> request/response ``all_to_all`` over the memory axis
                      carrying 1KB node rows (one round per tree level)
  offload RPC      -> one request/response ``all_to_all`` carrying keys in
                      and values out; the owner walks its local block
  compute-side     -> per-chip set-associative arrays; FIFO-within-set is
  cache               the vectorized form of the paper's cooling map
                      (bucket == set), lazy admission via key-hash bits

The offload decision replaces the paper's per-op moving-average latency
estimates (which require wall-clock self-measurement, impossible in an
SPMD program) with running miss-rate EMAs and a byte-cost comparison —
the same ``l_p < (L+1) * (l_o + l_s) * c`` structure evaluated on
predicted bytes instead of measured latencies, made **per destination
memory column** by the unified engine: ``DexState.miss_ema`` tracks one
EMA per (column, level) and each batch's per-column lane groups choose
fetch or offload independently (core/engine.py, DESIGN.md §7).

This module holds the mesh plane's shared state (config, state pytree,
stat indices) and the thin lookup wrapper; the per-chip cache machinery
(``DexCache``, probe/admit, ``cached_fetch_level`` and the pluggable
``CachePolicy`` layer) lives in core/fleet_cache.py, and the execution
dataflow for all four ops in core/engine.py.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.compat import make_mesh_compat
from repro.core.fleet_cache import (  # noqa: F401  (re-exported compat names)
    P_ADMIT_LEAF_PCT,
    DexCache,
    cached_fetch_level,
    init_cache,
)
from repro.core.nodes import FANOUT, KEY_MAX
from repro.core.pool import (
    PoolMeta,
    SubtreePool,
    blocks,
    initial_succ,
    Words,
    pool_specs,
    split_words,
)

NODE_ROW_BYTES = FANOUT * 8 * 3  # keys + children + values on the wire
OFFLOAD_REQ_BYTES = 16
OFFLOAD_RESP_BYTES = 16

# stat counter indices — derived from the declarative metric registry
# (repro/obs/registry.py), which owns slot order, units, sim-plane mapping
# and paper provenance.  Adding a counter means adding a Metric there; the
# constants below follow automatically and can never alias an old slot.
from repro.obs import latency as _latency
from repro.obs import registry as _metric_registry

_stat_consts = _metric_registry.stat_constants()
STAT_OPS = _stat_consts["STAT_OPS"]
STAT_HITS = _stat_consts["STAT_HITS"]
STAT_FETCHES = _stat_consts["STAT_FETCHES"]
STAT_OFFLOADS = _stat_consts["STAT_OFFLOADS"]
STAT_DROPS = _stat_consts["STAT_DROPS"]
STAT_SPLITS = _stat_consts["STAT_SPLITS"]
STAT_WRITES = _stat_consts["STAT_WRITES"]
STAT_SMO_SPLITS = _stat_consts["STAT_SMO_SPLITS"]
STAT_DRAINS = _stat_consts["STAT_DRAINS"]
STAT_OFFLOAD_GROUPS = _stat_consts["STAT_OFFLOAD_GROUPS"]
STAT_FETCH_GROUPS = _stat_consts["STAT_FETCH_GROUPS"]
STAT_PIPE_STALLS = _stat_consts["STAT_PIPE_STALLS"]
STAT_PEER_HITS = _stat_consts["STAT_PEER_HITS"]
STAT_PEER_MISSES = _stat_consts["STAT_PEER_MISSES"]
STAT_RT_SKIPS = _stat_consts["STAT_RT_SKIPS"]
STAT_RT_MISPREDICTS = _stat_consts["STAT_RT_MISPREDICTS"]
N_STATS = _metric_registry.N_STATS
del _stat_consts


@dataclasses.dataclass(frozen=True)
class DexMeshConfig:
    """Static configuration for the mesh plane."""

    route_axes: Tuple[str, ...] = ("data",)   # compute-partition axes
    memory_axis: str = "model"                # pool-shard axis
    n_route: int = 1                          # product of route axis sizes
    n_memory: int = 1                         # memory axis size
    cache_sets: int = 256
    cache_ways: int = 4
    # paper §5.4: P_A — derived from Plane A's DEFAULT_P_ADMIT_LEAF via
    # core/fleet_cache.py so the two planes can never silently diverge
    p_admit_leaf_pct: int = P_ADMIT_LEAF_PCT
    route_capacity_factor: float = 2.0        # all_to_all bucket slack
    policy: str = "auto"                      # fetch | offload | auto
    offload_c: float = 1.3                    # cost coefficient (§6.1)
    ema_decay: float = 0.98
    # leaf-direct route table capacity (DESIGN.md §13).  0 statically prunes
    # the predictor from the engine program — the compiled descent is the
    # verbatim pre-route-table one.  >0 reserves that many fence-verified
    # (key-range -> leaf) entries, trained host-side by core/route_table.py
    route_table_slots: int = 0

    @property
    def n_devices(self) -> int:
        return self.n_route * self.n_memory

    @property
    def all_axes(self) -> Tuple[str, ...]:
        return self.route_axes + (self.memory_axis,)


class DexState(NamedTuple):
    pool: SubtreePool
    cache: DexCache
    boundaries: jax.Array  # [n_route + 1] int64, replicated
    miss_ema: jax.Array    # [Dev, n_memory, levels] f32 per-(destination
    #                        memory column, level) miss-rate EMA — the input
    #                        of the engine's per-group offload cost model
    #                        (core/engine.py); psum-synchronized so every
    #                        chip prices a column identically
    stats: jax.Array       # [Dev, N_STATS] int64
    versions: jax.Array    # [Dev, n_nodes] int32 per-node write version
    occupancy: jax.Array   # [S, C] int32 keys per node (pool-aligned shard)
    route_demand: jax.Array  # [Dev, n_route] int64 routed requests per
    #                          partition measured at the *source* chip —
    #                          counts shed lanes too, so unlike the served
    #                          STAT_OPS it never saturates at bucket
    #                          capacity (the repartition controller's load
    #                          signal, core/repartition.py)
    succ: jax.Array        # [Dev, n_nodes] int64 leaf successor gid (-1
    #                        ends the chain; scans follow this instead of
    #                        leaf-id arithmetic — on-mesh splits relocate
    #                        leaves into the free-list headroom)
    n_alloc: jax.Array     # [S] int32 per-subtree free-list watermark
    #                        (pool-aligned shard): next free local node id;
    #                        subtree_cap means the block is out of headroom
    #                        and its splits drain through the host path
    lat_hist: jax.Array    # [Dev, classes, paths, buckets] int64 per-lane
    #                        modeled-latency histogram (obs/latency.py owns
    #                        the schema).  Pure per-device scatter — no
    #                        collective touches it; host-side readers sum
    #                        over Dev like they do for ``stats``
    lat_audit: jax.Array   # [Dev, 2, n_memory, levels] f32 offload
    #                        cost-model audit: plane 0 = predicted fetch
    #                        bytes (EMA rule, recorded on device 0 only —
    #                        the decision is mesh-global), plane 1 =
    #                        realized fetch bytes (per device, summed
    #                        host-side).  obs/latency.audit_report turns
    #                        the pair into a mispricing report
    # leaf-direct route table (DESIGN.md §13): R = max(route_table_slots, 1)
    # fence-verified entries, replicated like ``boundaries``.  Entry i says
    # "keys in [rt_keys[i], rt_hi[i]) lived in leaf (rt_sub[i], rt_local[i])
    # when versions[gid] was rt_ver[i]" — the engine accepts the guess only
    # while both the bounds and that version still hold, so a stale or
    # poisoned table degrades to full descent, never to wrong answers.
    # rt_ver == -1 marks an inactive slot (rt_keys KEY_MAX sorts it last).
    rt_keys: jax.Array     # [R] int64 sorted fence-low keys
    rt_hi: jax.Array       # [R] int64 exclusive fence-high keys
    rt_sub: jax.Array      # [R] int32 predicted subtree
    rt_local: jax.Array    # [R] int32 predicted leaf local id
    rt_ver: jax.Array      # [R] int32 leaf version at training time


def init_state(
    pool: SubtreePool,
    meta: PoolMeta,
    cfg: DexMeshConfig,
    boundaries: np.ndarray,
) -> DexState:
    levels = meta.levels_in_subtree
    n_nodes = meta.n_subtrees_padded * meta.subtree_cap
    succ0 = jnp.asarray(initial_succ(meta))
    base = meta.base_cap if meta.base_cap > 0 else meta.subtree_cap
    return DexState(
        pool=pool,
        cache=init_cache(cfg),
        boundaries=jnp.asarray(boundaries, jnp.int64),
        miss_ema=jnp.ones((cfg.n_devices, cfg.n_memory, levels), jnp.float32),
        stats=jnp.zeros((cfg.n_devices, N_STATS), jnp.int64),
        versions=jnp.zeros((cfg.n_devices, n_nodes), jnp.int32),
        occupancy=_occupancy(pool.pool_keys),
        route_demand=jnp.zeros((cfg.n_devices, cfg.n_route), jnp.int64),
        succ=jnp.broadcast_to(succ0[None, :], (cfg.n_devices, n_nodes)),
        n_alloc=jnp.full((meta.n_subtrees_padded,), base, jnp.int32),
        lat_hist=jnp.zeros(
            (cfg.n_devices, _latency.N_CLASSES, _latency.N_PATHS,
             _latency.N_BUCKETS),
            jnp.int64,
        ),
        lat_audit=jnp.zeros(
            (cfg.n_devices, 2, cfg.n_memory, levels), jnp.float32
        ),
        rt_keys=jnp.full((max(cfg.route_table_slots, 1),), KEY_MAX, jnp.int64),
        rt_hi=jnp.full((max(cfg.route_table_slots, 1),), KEY_MAX, jnp.int64),
        rt_sub=jnp.zeros((max(cfg.route_table_slots, 1),), jnp.int32),
        rt_local=jnp.zeros((max(cfg.route_table_slots, 1),), jnp.int32),
        rt_ver=jnp.full((max(cfg.route_table_slots, 1),), -1, jnp.int32),
    )


@jax.jit
def _occupancy(pool_keys: Words) -> jax.Array:
    """Keys per node ``[S, C]`` of a key word plane: slots whose words are
    not ``KEY_MAX``'s (one fused pass, no int64 copy of the plane)."""
    kmax = split_words(KEY_MAX)
    real = (blocks(pool_keys.hi) != kmax.hi) | (blocks(pool_keys.lo) != kmax.lo)
    return jnp.sum(real, axis=-1, dtype=jnp.int32)


def make_dex_mesh(devices: "Sequence[jax.Device] | None" = None):
    """The route x memory mesh (axes ``("data", "model")``, the
    :class:`DexMeshConfig` defaults) over ``devices`` — all devices present
    when None.  The route axis takes the largest divisor of the device count
    not above its square root: 1 device -> 1x1, 4 -> 2x2, 8 -> 2x4.  Build
    the config from it as ``DexMeshConfig(n_route=mesh.shape["data"],
    n_memory=mesh.shape["model"])``."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices)
    n_route = max(d for d in range(1, math.isqrt(n) + 1) if n % d == 0)
    return make_mesh_compat((n_route, n // n_route), ("data", "model"),
                            devices=devices)


def state_shardings(mesh, cfg: DexMeshConfig):
    """NamedShardings for a DexState on ``mesh``."""
    dev = P(cfg.all_axes)

    def ns(spec):
        return jax.sharding.NamedSharding(mesh, spec)

    pool_spec = jax.tree.map(ns, pool_specs(cfg.memory_axis),
                             is_leaf=lambda s: isinstance(s, P))
    cache_spec = DexCache(
        tags=ns(dev), keys=ns(dev), children=ns(dev), values=ns(dev),
        fifo=ns(dev), ver=ns(dev),
    )
    return DexState(
        pool=pool_spec,
        cache=cache_spec,
        boundaries=ns(P()),
        miss_ema=ns(dev),
        stats=ns(dev),
        versions=ns(dev),
        occupancy=ns(P(cfg.memory_axis)),
        route_demand=ns(dev),
        succ=ns(dev),
        n_alloc=ns(P(cfg.memory_axis)),
        lat_hist=ns(dev),
        lat_audit=ns(dev),
        rt_keys=ns(P()),
        rt_hi=ns(P()),
        rt_sub=ns(P()),
        rt_local=ns(P()),
        rt_ver=ns(P()),
    )


# ---------------------------------------------------------------------------
# the sharded lookup (routing helpers shared with core/scan.py live in
# core/routing.py; the cache probe/admit/fetch machinery in
# core/fleet_cache.py)
# ---------------------------------------------------------------------------


def make_dex_lookup(meta: PoolMeta, cfg: DexMeshConfig, mesh):
    """Build the sharded lookup:
    ``(state, keys) -> (state, found, values, shed)``.

    A thin single-opcode wrapper over the unified mixed-op engine
    (:func:`repro.core.engine.make_dex_engine`): one route round, one
    version-checked cached descent, and — for columns whose per-group cost
    model picks the two-sided path — tagged offload messages in the fused
    ``all_to_all`` round.  ``keys`` is globally sharded over all mesh axes;
    results come back in the caller's lane order.  ``shed`` marks lanes
    that were load-shed by a routing bucket (their ``found``/``values`` are
    not answers — the caller retries them, and the repartition controller
    uses the drop counters to move partition boundaries so they stop
    happening).  Wrap with ``jax.jit`` (see serve/ and launch/).
    """
    from repro.core import engine as engine_mod  # deferred: engine imports us

    eng = engine_mod.make_dex_engine(meta, cfg, mesh, ops=("lookup",))

    def lookup(state: DexState, keys: jax.Array):
        keys = keys.astype(jnp.int64)
        opcodes = jnp.full(keys.shape, engine_mod.OP_LOOKUP, jnp.int32)
        new_state, r = eng(state, opcodes, keys, jnp.zeros_like(keys))
        return new_state, r.found, r.values, r.shed

    return lookup
