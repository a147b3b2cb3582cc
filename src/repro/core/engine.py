"""Unified mixed-op execution engine for the mesh plane (Plane B).

Before this module every mixed YCSB batch paid three separately-jitted
programs — ``make_dex_lookup``, ``make_dex_update``/``make_dex_insert`` and
``make_dex_scan`` — each with its own route round, its own cached descent
and its own request/response ``all_to_all`` machinery, and the offload
decision (§6.1) was a single batch-global, lookup-only gate.
:func:`make_dex_engine` collapses all of that into **one SPMD program** that
consumes a per-lane *opcode plane* (``OP_LOOKUP`` / ``OP_UPDATE`` /
``OP_INSERT`` / ``OP_SCAN``) next to the key and value planes and executes
the whole mixed batch through:

  1. **one shared route round** (``routing.route_owners`` + a single
     ``route_exchange`` pair) for every opcode;
  2. **one shared version-checked cached descent** — inner levels for all
     lanes, the leaf level for lookup/update/scan lanes (inserts stop above
     the leaf, exactly like the old write path), with the per-chip cache
     probe/admit and coalesced remote fetches of ``cached_fetch_level``;
  3. scan lanes only: the successor-chain sibling hops of core/scan.py;
  4. **one fused request/response ``all_to_all`` pair** over the memory
     axis carrying *tagged mixed-op messages* — CAS-style updates and
     slack-slot inserts from the fetched path next to offloaded
     lookup/update/insert walks — applied by the owning memory column in a
     single conflict-resolved batch (``write._apply_leaf_writes``).

``make_dex_lookup`` / ``make_dex_update`` / ``make_dex_insert`` /
``make_dex_scan`` are thin single-opcode wrappers over this engine (the
static ``ops=`` set prunes dead machinery at trace time, so a lookup-only
program is as lean as the old one).

**Per-group cost-aware offloading (§6.1, refined).**  The old gate compared
one predicted per-lane fetch cost against a *once-per-batch* RPC price and
forced the whole batch down one branch.  The engine decides **per
destination memory column**: ``DexState.miss_ema`` is now a per-(column,
level) miss-rate EMA, and each column's group of live non-scan lanes
compares

  ``fetch(g) = sum_l min(n_live(g), nodes_l) * ema[g, l] * NODE_ROW_BYTES * c``
  ``rpc(g)   = n_live(g) * (OFFLOAD_REQ_BYTES + OFFLOAD_RESP_BYTES)``

— the RPC side now scales with the group's live-lane count (the fused plane
sends per-lane tagged messages, so a mostly-inactive KEY_MAX batch no
longer sees a spuriously cheap once-per-batch RPC price), while the fetch
side is capped by the column's node population per level (coalesced reads
never exceed the distinct nodes).  A cold column (EMA near 1) offloads
while a warm one fetches *within the same batch*; scans never offload
(§7), and offloaded inserts that would split shed ``STATUS_SPLIT`` to
core/smo.py exactly like fetched-path ones (the paper's SMO fallback
rule).  Group decisions are made on mesh-global live counts (one tiny
psum), so they are uniform across devices and countable once per batch
(``STAT_OFFLOAD_GROUPS`` / ``STAT_FETCH_GROUPS``, cross-validated against
``Simulator`` group accounting in benchmarks/fig13_mesh_engine.py).

Batch semantics match the phased sequential replay the benchmarks and
tests use: reads (lookups, scans) observe the pre-batch index, then
updates apply, then inserts — enforced by a phase-offset batch priority in
the conflict resolution.

Continuous-service pipelining (``pipeline=True``)
-------------------------------------------------
The batch-synchronous program above is one blocking round trip: the mesh
idles through the fused ``all_to_all`` pair and the leaf apply of batch N
before batch N+1's route round may start.  Outback's observation — that
communication rounds, not compute, bound disaggregated-memory throughput —
says exactly this gap is the throughput ceiling.  ``make_dex_engine(...,
pipeline=True)`` therefore returns an :class:`EnginePipeline`: a two-stage
software pipeline over a batch queue in which **step s executes batch
B_s's front half (route round + version-checked cached descent + scan
hops) fused with batch B_{s-1}'s back half (fused request/response
``all_to_all`` + leaf apply + result return)** inside one jitted dispatch.
The collectives of B_{s-1}'s write round are hidden under B_s's descent.

Correctness over the one-batch overlap window:

* **Navigation is static within a pipeline run.**  The leaf apply mutates
  only leaf key/value rows and occupancy; splits shed ``STATUS_SPLIT`` to
  the SMO path (settled between pipeline flushes), so inner nodes, the top
  tree and leaf *identity* never move while batches are in flight.  A
  front-half descent therefore always lands on the correct leaf gid — only
  the leaf's *contents* can be one batch stale.
* **Version stamps detect the overlap.**  The front half stamps the leaf
  version (and each scan hop's version) it descended through into the
  carry.  When the back half runs one step later it re-reads the version
  table — which by then includes the overlapped batch's bumps — and any
  mismatch marks the lane *stale-forced*: lookups and updates are forced
  onto the two-sided offload tags (``MSG_OFF_LOOKUP``/``MSG_OFF_UPDATE``),
  so the owning memory column re-resolves them against the authoritative
  post-overlap pool.  Inserts never need forcing: ``MSG_INSERT`` carries
  only the (stable) leaf gid and the apply re-searches the leaf anyway.
* **Writers stay ordered.**  The phase-offset batch priorities already
  order conflicting writers *within* a batch; across the overlap window
  batches apply strictly in order (step s applies B_{s-1} before step s+1
  applies B_s), so the sequential batch order is preserved exactly.
* **Conservative conflict stall.**  Scan lanes whose window crossed a leaf
  whose version moved are stall-shed (``taken = -1``, ``shed``) onto the
  repo's standard shed-and-retry lane — the conservative fallback for the
  one shape whose partial window cannot be patched cheaply.

Stale-forced lanes and stall-shed scans are counted in
``STAT_PIPE_STALLS`` (always 0 in batch-synchronous mode).  Results, pool,
occupancy and version evolution are bit-identical to the synchronous
engine run batch-by-batch on the same inputs (modulo shed-and-retry lanes,
which both modes surface through ``EngineResult.shed``); per-chip cache
contents and hit/fetch counters may diverge inside the overlap window —
a performance artifact, not a correctness one.
"""

from __future__ import annotations

import contextlib
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import fleet_cache
from repro.core import routing
from repro.core.dex import (
    NODE_ROW_BYTES,
    N_STATS,
    OFFLOAD_REQ_BYTES,
    OFFLOAD_RESP_BYTES,
    STAT_DROPS,
    STAT_FETCH_GROUPS,
    STAT_FETCHES,
    STAT_HITS,
    STAT_OFFLOAD_GROUPS,
    STAT_OFFLOADS,
    STAT_OPS,
    STAT_PEER_HITS,
    STAT_PEER_MISSES,
    STAT_PIPE_STALLS,
    STAT_RT_MISPREDICTS,
    STAT_RT_SKIPS,
    STAT_SPLITS,
    STAT_WRITES,
    DexMeshConfig,
    DexState,
)
from repro.core.fleet_cache import DexCache, cached_fetch_level
from repro.core.nodes import FANOUT, KEY_MAX
from repro.core.pool import PoolMeta, node_rows, pool_specs, top_walk
from repro.core.write import (
    STATUS_MISS,
    STATUS_OK,
    STATUS_SHED,
    STATUS_SPLIT,
    _apply_leaf_writes,
)
from repro.kernels.leaf_scan import leaf_scan
from repro.kernels.ref import leaf_scan_ref
from repro.obs import latency as obs_latency

# engine opcodes == the YCSB trace opcodes (data/ycsb.py), so a generated
# mixed workload slice feeds the engine directly
OP_LOOKUP, OP_UPDATE, OP_INSERT, OP_SCAN = 0, 1, 2, 3

ALL_OPS = ("lookup", "update", "insert", "scan")
DEFAULT_MAX_COUNT = 128

# fused-plane message tags (field 0 of a request record)
MSG_NONE = 0          # no request from this lane (or bucket padding)
MSG_UPDATE = 1        # fetched-path CAS update: gid known from the descent
MSG_INSERT = 2        # fetched-path slack-slot insert: gid from the descent
MSG_OFF_LOOKUP = 3    # offloaded lookup: owner walks its block
MSG_OFF_UPDATE = 4    # offloaded update: owner walks, then CAS
MSG_OFF_INSERT = 5    # offloaded insert: owner walks, then slack merge
MSG_PEEK = 6          # peer peek: owner answers a sibling's leaf miss from
#                       its own version-checked cache, else its block walk
REQ_FIELDS = 6        # (tag, gid, subtree, key, value, prio)
RESP_HEAD = 4         # (status, value, gid, leaf-took-inserts flag — the
#                       flag doubles as the peer-cache-hit bit for MSG_PEEK
#                       lanes) ahead of the merged value row


def scan_hops(meta: PoolMeta, max_count: int) -> int:
    """Leaves that may contribute to a ``max_count``-record scan: the start
    leaf (which can contribute as little as nothing when the start key lies
    above its last record) plus enough minimally-filled leaves for the rest
    (``min_leaf_fill``: on-mesh splits can leave leaves half-full).  This is
    only the static loop bound — per-lane collected-count masking stops each
    lane's remote reads as soon as its count is covered."""
    return 1 + -(-max_count // meta.min_leaf_fill)


def distinct_fetched_per_column(gid, fetched, n_nodes: int, subtree_cap: int,
                                s_per: int, n_memory: int) -> jax.Array:
    """Distinct node ids among the ``fetched`` lanes, counted per memory
    column (``(gid // subtree_cap) // s_per``) as ``[n_memory]`` float32:
    the realized side of the offload audit (DESIGN.md §12), since the mesh
    coalesces duplicate gids into one message.  The other lanes are masked
    to the ``n_nodes`` sentinel; the gids are sorted and the first of each
    run counts once, so the cost is O(lanes), not O(n_nodes).  Node ids
    are sorted and divided as int32 (the chip has no 64-bit lanes): every
    chip holds a version per node of the pool, so they fit."""
    g = jnp.where(fetched & (gid < n_nodes), gid, n_nodes).astype(jnp.int32)
    g = jnp.sort(g)
    first = jnp.concatenate([jnp.ones((1,), bool), g[1:] != g[:-1]])
    first = first & (g < n_nodes)
    col = jnp.where(first, (g // subtree_cap) // s_per, 0).astype(jnp.int32)
    return jnp.zeros((n_memory,), jnp.float32).at[col].add(
        first.astype(jnp.float32)
    )


@contextlib.contextmanager
def _lat_scope(part: str):
    """A block of the modelled-cost ledger (obs/latency.py): its ops under
    ``dex/lat/<part>``, and any collective traced in it counted under the
    ``dex/lat`` phase, where benchmarks assert there is none."""
    with jax.named_scope("dex/lat/" + part), routing.trace_phase("dex/lat"):
        yield


class EngineResult(NamedTuple):
    """Per-lane results of one mixed batch, in the caller's lane order.

    ``found``/``values`` answer lookup lanes; ``status`` answers write lanes
    (``STATUS_OK``/``STATUS_MISS``/``STATUS_SHED``/``STATUS_SPLIT``);
    ``shed`` marks lanes load-shed anywhere along their path (retry them);
    ``scan_keys``/``scan_values``/``taken`` answer scan lanes and are
    ``None`` when the engine was built without ``"scan"`` in ``ops``."""

    found: jax.Array
    values: jax.Array
    status: jax.Array
    shed: jax.Array
    scan_keys: Optional[jax.Array] = None
    scan_values: Optional[jax.Array] = None
    taken: Optional[jax.Array] = None


def _empty_result(b, mc, has_scan):
    return EngineResult(
        found=jnp.zeros((b,), bool),
        values=jnp.zeros((b,), jnp.int64),
        status=jnp.full((b,), STATUS_MISS, jnp.int32),
        shed=jnp.zeros((b,), bool),
        scan_keys=jnp.full((b, mc), KEY_MAX, jnp.int64) if has_scan else None,
        scan_values=jnp.zeros((b, mc), jnp.int64) if has_scan else None,
        taken=jnp.zeros((b,), jnp.int32) if has_scan else None,
    )


class EnginePipeline:
    """Two-stage software pipeline over a batch queue (prologue /
    steady-state / drain).

    ``push(opcodes, keys, values)`` dispatches one fused step — the new
    batch's front half overlapped with the previous batch's back half —
    and returns the **previous** batch's :class:`EngineResult` (device
    futures; ``np.asarray`` them to block).  The first push primes the
    pipeline and returns ``None``; ``drain()`` pushes an inactive batch to
    flush the last in-flight back half and returns the final result.
    Every pushed batch must share one lane width.

    ``step_fn`` (the unjitted step) and ``init_carry(b)`` are exposed so
    benchmarks can run ``routing.trace_collective_counts`` over one steady
    -state step; ``plan`` carries the static collective structure like the
    synchronous engine's.
    """

    def __init__(self, step, init_carry, plan):
        self.step_fn = step
        self.init_carry = init_carry
        self.plan = plan
        self._step = jax.jit(step)
        self._state = None
        self._carry = None
        self._width = None
        self._primed = False

    @property
    def state(self):
        """Index state as of the last completed back half."""
        return self._state

    def start(self, state: DexState) -> "EnginePipeline":
        """Begin a pipeline run from ``state``; resets any prior carry."""
        self._state = state
        self._carry = None
        self._primed = False
        return self

    def push(self, opcodes, keys, values) -> Optional[EngineResult]:
        if self._state is None:
            raise RuntimeError("EnginePipeline.push before start(state)")
        b = int(keys.shape[0])
        if b == 0:
            raise ValueError("pipeline batches must be non-empty")
        if self._carry is None:
            self._width = b
            self._carry = self.init_carry(b)
        elif b != self._width:
            raise ValueError(
                f"pipeline batches must share one width: {b} != {self._width}"
            )
        was_primed = self._primed
        self._state, self._carry, result = self._step(
            self._state, self._carry, opcodes, keys, values
        )
        self._primed = True
        # the result lanes of the very first step answer the all-inactive
        # prologue carry, not a caller batch
        return result if was_primed else None

    def drain(self) -> Optional[EngineResult]:
        """Flush the in-flight batch; afterwards the next push re-primes."""
        if self._state is None or not self._primed:
            return None
        b = self._width
        self._state, self._carry, result = self._step(
            self._state,
            self._carry,
            jnp.zeros((b,), jnp.int32),
            jnp.full((b,), KEY_MAX, jnp.int64),
            jnp.zeros((b,), jnp.int64),
        )
        self._carry = None
        self._primed = False
        return result

    def run(self, state: DexState, batches):
        """Convenience: stream ``batches`` (an iterable of ``(opcodes,
        keys, values)``) through a full prologue/steady-state/drain cycle;
        returns ``(state, [EngineResult per batch, in order])``."""
        self.start(state)
        results = []
        for opc, kk, vv in batches:
            r = self.push(opc, kk, vv)
            if r is not None:
                results.append(r)
        r = self.drain()
        if r is not None:
            results.append(r)
        return self._state, results


def make_dex_engine(
    meta: PoolMeta,
    cfg: DexMeshConfig,
    mesh,
    *,
    ops: Tuple[str, ...] = ALL_OPS,
    max_count: int = DEFAULT_MAX_COUNT,
    use_kernel: bool = True,
    interpret: "bool | None" = None,
    pipeline: bool = False,
    cache_policy: "fleet_cache.CachePolicy | None" = None,
):
    """Build the unified mixed-op program:
    ``(state, opcodes, keys, values) -> (state, EngineResult)``.

    ``opcodes``/``keys``/``values`` are [B] lanes globally sharded over all
    mesh axes; ``keys == KEY_MAX`` lanes are inactive no-ops regardless of
    opcode.  The ``values`` plane is overloaded per opcode: update/insert
    lanes carry the write payload, scan lanes carry their record count
    (clipped to ``max_count``), lookup lanes ignore it.  ``ops`` statically
    prunes machinery: opcodes outside the set are treated as inactive, and
    e.g. a ``("lookup",)`` engine contains no write round or scan hops —
    this is how the thin per-op wrappers stay as lean as the programs they
    replaced.  Wrap with ``jax.jit``.

    With ``pipeline=True`` the same front/back machinery is recomposed as
    one fused *pipeline step* — batch N+1's front half next to batch N's
    back half — and an :class:`EnginePipeline` driver is returned instead
    of the synchronous callable (see the module docstring for the overlap
    -window correctness argument).

    The returned function carries a ``plan`` attribute — the static
    collective structure ``{"route_rounds", "fused_pairs",
    "descent_levels", "scan_hops"}`` — which benchmarks print next to the
    traced collective counts (``routing.trace_collective_counts``).

    ``cache_policy`` selects the per-chip fleet-cache policy
    (:mod:`repro.core.fleet_cache`).  ``None`` or a
    :func:`fleet_cache.uniform_policy` compiles the verbatim pre-policy
    program — bit-identical outputs; a :func:`fleet_cache.divergent_policy`
    enables column-affinity/demand-biased admission and peer peeks
    (``MSG_PEEK`` riding the existing fused round: zero extra collectives).
    """
    for o in ops:
        if o not in ALL_OPS:
            raise ValueError(f"unknown op {o!r}; options: {ALL_OPS}")
    has_lookup = "lookup" in ops
    has_update = "update" in ops
    has_insert = "insert" in ops
    has_scan = "scan" in ops
    has_writes = has_update or has_insert
    # lanes that can offload (scans never do, §7)
    has_offloadable = has_lookup or has_writes
    # the pipelined overlap window resolves stale lookup/update lanes by
    # forcing them onto the two-sided tags, so those branches must be
    # compiled even under policy="fetch" whenever forcing can occur
    needs_force = bool(pipeline) and has_writes and (has_lookup or has_update)
    # policy="fetch" statically prunes every two-sided branch: no offload
    # tags, no owner-side block walk inside the fused round
    may_offload = has_offloadable and (cfg.policy != "fetch" or needs_force)
    # the one-sided descent is dead weight only when every offloadable lane
    # is forced two-sided and no scan lanes exist
    do_descent = has_scan or (cfg.policy != "offload") or not has_offloadable
    # the leaf level of the descent serves lookup/update answers and scan
    # hop 0; insert lanes stop above it
    do_leaf = has_lookup or has_update or has_scan
    # peer peeks (MSG_PEEK) only exist for descent lookup lanes under a
    # policy with peek budget — statically pruned otherwise
    may_peek = (
        has_lookup and do_descent and do_leaf
        and fleet_cache.peeks_enabled(cache_policy)
    )
    # leaf-direct route table (DESIGN.md §13): statically pruned when the
    # config reserves no slots, so the default program is the verbatim
    # descent-only one (bit-identical outputs AND collective counts)
    use_rt = cfg.route_table_slots > 0 and do_descent
    do_fused = has_writes or may_offload or may_peek
    levels = meta.levels_in_subtree
    hops = scan_hops(meta, max_count) if has_scan else 0
    mc = max_count
    s_per = meta.n_subtrees_padded // cfg.n_memory
    # per-level node population of one column's subtrees: the fetch side of
    # the group cost model is capped by it (coalesced reads never exceed
    # the distinct nodes of a level)
    level_nodes = [
        float(s_per * min(meta.per_node**lvl, meta.leaves_per_subtree))
        for lvl in range(levels)
    ]
    # carry leaves crossing a pipeline step, in fixed order (all lane-plane
    # sharded): the front half's routed batch, descent answers and version
    # stamps, consumed by the matching back half one step later
    carry_keys = [
        "q", "val", "opc", "pr", "subtree", "offl", "gid", "found", "vleaf",
        "shed", "vseen", "lane", "dropr", "cost", "fmiss",
    ]
    if may_peek:
        carry_keys += ["peek"]
    if has_scan:
        carry_keys += ["sck", "scv", "taken", "hgid", "hver"]

    def _run_front(pool, cache, boundaries, miss_ema, stats, demand,
                   versions, succ, rtk, rth, rts, rtl, rtv,
                   opcodes, keys, values, *, stamp):
        """Front half: route round, top walk + per-group offload decision,
        version-checked cached descent and scan hops.  ``stamp=True``
        (pipeline mode) records the version of every leaf (and scan hop)
        the descent observed, for the back half's overlap-window check."""
        b = keys.shape[0]
        n_route = cfg.n_route

        # Every op of the step sits under exactly one ``dex/<family>`` scope
        # (``plan["phases"]``), never one inside another: a profiler trace
        # attributes an op to the first ``dex/`` component of its op_name.
        # --- 1. ONE shared route round for every opcode --------------------
        with jax.named_scope("dex/lanes"):
            vers = versions[0]
            succ_t = succ[0]
            dev = routing.device_linear_index(cfg, mesh)
            lane_prio = (
                dev.astype(jnp.int64) * b + jnp.arange(b, dtype=jnp.int64)
            )
            # phase-offset priority: all updates replay before all inserts,
            # the phased batch order the host-mirror validation uses
            phase = jnp.where(
                opcodes == OP_INSERT, jnp.int64(cfg.n_devices) * b,
                jnp.int64(0),
            )
            prio0 = lane_prio + phase
        n_nodes_total = vers.shape[0]
        with jax.named_scope("dex/locate"):
            owner, dem = routing.route_owners(boundaries, keys, n_route)
            new_demand = demand + dem
        cap = routing.route_capacity(b, n_route, cfg.route_capacity_factor)
        with jax.named_scope("dex/lanes"):
            payload = jnp.stack(
                [keys, values, opcodes.astype(jnp.int64), prio0], axis=-1
            )                                               # [B, 4]
            buf, lane, dropped_r = routing.pack_by_dest(
                payload, owner, n_route, cap
            )
            # inactive lanes share the OOB sentinel bucket; its overflow is
            # meaningless (see routing.route_owners)
            dropped_r = dropped_r & (keys != KEY_MAX)
        with jax.named_scope("dex/route"):
            routed = routing.route_exchange(buf, cfg, mesh)  # [n_route, cap, 4]
        with jax.named_scope("dex/lanes"):
            q = routed[..., 0].reshape(-1)                  # [Q]
            val = routed[..., 1].reshape(-1)
            opc = routed[..., 2].reshape(-1).astype(jnp.int32)
            pr = routed[..., 3].reshape(-1)
            live = q != KEY_MAX
            is_scan = (
                live & (opc == OP_SCAN) if has_scan
                else jnp.zeros(q.shape, bool)
            )

        # --- 2. replicated top-tree walk + per-group offload decision ------
        with jax.named_scope("dex/locate"):
            subtree = top_walk(pool, meta, q)
            subtree = jnp.where(live, subtree, 0)
            col = (subtree // s_per).astype(jnp.int32)
        with jax.named_scope("dex/offload"):
            ema = miss_ema[0]                               # [n_mem, levels]
            if has_offloadable and cfg.policy == "auto":
                # group = destination memory column; live counts are psum'd
                # so the decision is uniform across devices (and countable
                # once)
                offable = live & ~is_scan
                n_live_c = (
                    jnp.zeros((cfg.n_memory,), jnp.int64)
                    .at[col].add(offable.astype(jnp.int64))
                )
                n_live_c = jax.lax.psum(n_live_c, cfg.all_axes)
                nf = n_live_c.astype(jnp.float32)
                caps = jnp.minimum(
                    nf[:, None], jnp.asarray(level_nodes, jnp.float32)[None, :]
                )                                           # [n_mem, levels]
                fetch_cost = (
                    jnp.sum(caps * ema, axis=-1) * NODE_ROW_BYTES
                    * cfg.offload_c
                )
                rpc_cost = nf * float(OFFLOAD_REQ_BYTES + OFFLOAD_RESP_BYTES)
                want_off_c = fetch_cost > rpc_cost          # [n_mem] bool
                grp_live = n_live_c > 0
            elif has_offloadable and cfg.policy == "offload":
                offable = live & ~is_scan
                n_live_c = (
                    jnp.zeros((cfg.n_memory,), jnp.int64)
                    .at[col].add(offable.astype(jnp.int64))
                )
                n_live_c = jax.lax.psum(n_live_c, cfg.all_axes)
                want_off_c = jnp.ones((cfg.n_memory,), bool)
                grp_live = n_live_c > 0
            else:
                want_off_c = jnp.zeros((cfg.n_memory,), bool)
                grp_live = jnp.zeros((cfg.n_memory,), bool)
            offl = want_off_c[col] & live & ~is_scan if has_offloadable else (
                jnp.zeros(q.shape, bool)
            )
            n_off_groups = jnp.sum(want_off_c & grp_live).astype(jnp.int64)
            n_fetch_groups = jnp.sum(~want_off_c & grp_live).astype(jnp.int64)

        # --- leaf-direct route-table probe (DESIGN.md §13) -----------------
        # one searchsorted over the replicated trained table maps the key
        # straight to a predicted leaf; the fence-key bounds + version fence
        # accept or reject the guess BEFORE any descent level runs.  An
        # accepted lane skips every inner-level fetch round and probes the
        # predicted leaf directly (under the same version-checked cache
        # machinery); a rejected lane falls back to the full cached descent
        # — so a stale, partial or poisoned table costs mispredict counts,
        # never answers.  Scans keep their full descent (their window
        # machinery consumes the descent's leaf row anyway).
        with jax.named_scope("dex/locate"):
            acc = jnp.zeros(q.shape, bool)
            n_rt_skips = jnp.int64(0)
            n_rt_mis = jnp.int64(0)
            if use_rt:
                ridx, p_sub, p_loc = routing.rt_predict(rtk, rts, rtl, q)
                elig = live & ~is_scan & ~offl
                rt_guess, acc, _pred_gid = fleet_cache.rt_accept(
                    meta, rtk, rth, rts, rtl, rtv, vers, ridx, subtree, q,
                    elig,
                )
                n_rt_mis = jnp.sum(rt_guess & ~acc).astype(jnp.int64)
                # an accepted lane skips all inner levels within the subtree
                n_rt_skips = jnp.sum(acc).astype(jnp.int64) * (levels - 1)

        # --- per-lane cost ledger + offload cost-model audit ----------------
        # (obs/latency.py, DESIGN.md §12).  ``cost`` accumulates the modeled
        # seconds each lane spends — priced by the same constants the
        # simulator's op_clock uses — and is binned on-device in the back
        # half; ``fmiss`` remembers whether any level paid a remote fetch
        # (the remote_fetch path bit).  The replicated top walk prices like
        # the simulator's warm top-tree cache hits.
        with _lat_scope("price"):
            cost = live.astype(jnp.float32) * (
                obs_latency.T_CACHED * float(meta.top_height)
            )
            fmiss = jnp.zeros(q.shape, bool)
        audit = has_offloadable and cfg.policy == "auto"
        with _lat_scope("audit"):
            a_upd = jnp.zeros((2, cfg.n_memory, levels), jnp.float32)
            if audit:
                # predicted fetch bytes per (column, level) under the EMA
                # rule, recorded for the columns the model actually priced
                # onto the fetch side; the decision is mesh-global (psum'd
                # counts), so device 0 records it once.  The realized bytes
                # (distinct fetched nodes) are added level by level below.
                pred_cl = caps * ema * NODE_ROW_BYTES * cfg.offload_c
                fetch_dec = (grp_live & ~want_off_c).astype(jnp.float32)
                a_upd = a_upd.at[0].set(
                    (dev == 0).astype(jnp.float32) * fetch_dec[:, None]
                    * pred_cl
                )

        # --- 3. ONE shared version-checked cached descent ------------------
        new_cache = cache
        with jax.named_scope("dex/lanes"):
            n_fetch = jnp.int64(0)
            n_hit = jnp.int64(0)
            shed = jnp.zeros(q.shape, bool)
            miss_cl = jnp.zeros((cfg.n_memory, levels), jnp.float32)
            want_cl = jnp.zeros((cfg.n_memory, levels), jnp.float32)
            peeked_leaf = jnp.zeros(q.shape, bool)
        with jax.named_scope("dex/locate"):
            fetchable = live & ~offl
            local = jnp.zeros(q.shape, jnp.int32)
            found_leaf = jnp.zeros(q.shape, bool)
            vals_leaf = jnp.zeros(q.shape, jnp.int64)
            rows_k_leaf = jnp.full(q.shape + (FANOUT,), KEY_MAX, jnp.int64)
            rows_v_leaf = jnp.zeros(q.shape + (FANOUT,), jnp.int64)
            # divergent policies scale the admission dice by the chip's
            # share of its own measured route demand (chip-local; no
            # collective)
            dboost = fleet_cache.demand_boost(
                cache_policy, cfg, demand, routing.route_linear_index(cfg, mesh)
            )
        if do_descent:
            descent_levels = levels if do_leaf else levels - 1
            for lvl in range(descent_levels):
                leaf_lvl = lvl == levels - 1
                peek_elig = peek_budget = None
                with jax.named_scope("dex/locate"):
                    if leaf_lvl:
                        if use_rt:
                            # accepted lanes land directly on the predicted
                            # leaf
                            local = jnp.where(acc, p_loc, local)
                        want = fetchable & (
                            (opc == OP_LOOKUP) | (opc == OP_UPDATE) | is_scan
                        )
                        p_ok = fleet_cache.leaf_admit(
                            meta, cfg, cache_policy,
                            meta.node_gid(subtree, local),
                            stats[0, STAT_OPS] + jnp.arange(q.shape[0]),
                            dev=dev, boost=dboost,
                        )
                        if may_peek:
                            # a leaf miss whose subtree another column owns
                            # may ask that column's cache instead of
                            # row-fetching
                            my_col = jax.lax.axis_index(cfg.memory_axis)
                            peek_elig = (
                                want & (opc == OP_LOOKUP) & (col != my_col)
                            )
                            peek_budget = fleet_cache.device_peek_budget(
                                cache_policy, dev
                            )
                    else:
                        # route-table-accepted lanes skip the inner fetch
                        # rounds
                        want = fetchable & ~acc if use_rt else fetchable
                        p_ok = jnp.ones(q.shape, bool)
                    gid = meta.node_gid(subtree, local)
                with jax.named_scope(f"dex/descent/l{lvl}"):
                    rows_k, rows_c, rows_v, hit, miss, f_drop, n_msgs, \
                        new_cache, peeked = cached_fetch_level(
                            pool, meta, cfg, new_cache, vers, gid, want, p_ok,
                            peek_elig, peek_budget,
                        )
                if leaf_lvl and may_peek:
                    peeked_leaf = peeked
                # ledger: a fresh cache hit prices one cached access, a
                # served miss one remote read; peeked lanes fetch nothing
                # here (their two-sided trip prices in the back half) and
                # bucket-overflowed lanes got no row
                with _lat_scope("price"):
                    fetched = miss & ~f_drop
                    if leaf_lvl and may_peek:
                        fetched = fetched & ~peeked
                    cost = cost + (
                        hit.astype(jnp.float32) * obs_latency.T_CACHED
                        + fetched.astype(jnp.float32) * obs_latency.T_READ
                    )
                    fmiss = fmiss | fetched
                if audit:
                    with _lat_scope("audit"):
                        cnt_c = distinct_fetched_per_column(
                            gid, fetched & ~is_scan, n_nodes_total,
                            meta.subtree_cap, s_per, cfg.n_memory,
                        )
                        a_upd = a_upd.at[1, :, lvl].add(
                            cnt_c * float(NODE_ROW_BYTES)
                        )
                with jax.named_scope("dex/lanes"):
                    shed = shed | f_drop
                    n_fetch = n_fetch + n_msgs
                    n_hit = n_hit + jnp.sum(hit).astype(jnp.int64)
                    # per-(column, level) miss observation; scan lanes leave
                    # the EMA untouched (they never offload)
                    obs = (want & ~is_scan).astype(jnp.float32)
                    miss_cl = miss_cl.at[col, lvl].add(
                        miss.astype(jnp.float32) * obs
                    )
                    want_cl = want_cl.at[col, lvl].add(obs)
                with jax.named_scope("dex/locate"):
                    if not leaf_lvl:
                        cnt = jnp.sum(rows_k <= q[:, None], axis=-1)
                        slot = jnp.maximum(cnt - 1, 0).astype(jnp.int32)
                        local = jnp.take_along_axis(
                            rows_c, slot[:, None], axis=-1
                        )[:, 0]
                    else:
                        eq = rows_k == q[:, None]
                        found_leaf = jnp.any(eq, axis=-1) & want
                        vals_leaf = jnp.sum(jnp.where(eq, rows_v, 0), axis=-1)
                        rows_k_leaf, rows_v_leaf = rows_k, rows_v
        with jax.named_scope("dex/locate"):
            if use_rt and not do_leaf:
                # insert-only engines stop above the leaf; accepted lanes
                # still land their MSG_INSERT on the predicted leaf
                local = jnp.where(acc, p_loc, local)
            leaf_gid = meta.node_gid(subtree, local)

        # --- 4. scan lanes: successor-chain sibling hops -------------------
        hop_gids = []
        hop_vers = []
        if has_scan:
            with jax.named_scope("dex/lanes"):
                cnt_s = jnp.clip(
                    jnp.where(is_scan, val, 0), 0, mc
                ).astype(jnp.int32)
                window_k = [jnp.where(is_scan[:, None], rows_k_leaf, KEY_MAX)]
                window_v = [jnp.where(is_scan[:, None], rows_v_leaf, 0)]
                collected = jnp.sum(
                    ((window_k[0] != KEY_MAX) & (window_k[0] >= q[:, None]))
                    .astype(jnp.int32),
                    axis=-1,
                )
            in_range = is_scan
            gid_h = leaf_gid
            for h in range(1, hops):
                with jax.named_scope("dex/locate"):
                    nxt = succ_t[jnp.where(in_range, gid_h, 0)]
                    in_range = in_range & (collected < cnt_s) & (nxt >= 0)
                    gid_h = jnp.where(in_range, nxt, gid_h)
                    gid = jnp.where(in_range, gid_h, 0)
                    p_ok = fleet_cache.leaf_admit(
                        meta, cfg, cache_policy, gid,
                        stats[0, STAT_OPS] + h + jnp.arange(q.shape[0]),
                        dev=dev, boost=dboost,
                    )
                if stamp:
                    with jax.named_scope("dex/coherence"):
                        hop_gids.append(
                            jnp.where(in_range, gid_h, -1).astype(jnp.int64)
                        )
                        hop_vers.append(jnp.where(in_range, vers[gid], 0))
                with jax.named_scope(f"dex/scan/h{h}"):
                    rows_k, _rows_c, rows_v, hit, miss, f_drop, n_msgs, \
                        new_cache, _peeked = cached_fetch_level(
                            pool, meta, cfg, new_cache, vers, gid, in_range,
                            p_ok,
                        )
                # ledger: each executed hop prices like one more leaf level
                # plus the per-hop local search the simulator books
                with _lat_scope("price"):
                    fetched_h = miss & ~f_drop
                    cost = cost + (
                        hit.astype(jnp.float32) * obs_latency.T_CACHED
                        + fetched_h.astype(jnp.float32) * obs_latency.T_READ
                        + in_range.astype(jnp.float32) * obs_latency.T_LOCAL
                    )
                    fmiss = fmiss | fetched_h
                with jax.named_scope("dex/lanes"):
                    shed = shed | f_drop
                    n_fetch = n_fetch + n_msgs
                    n_hit = n_hit + jnp.sum(hit).astype(jnp.int64)
                    rows_k = jnp.where(in_range[:, None], rows_k, KEY_MAX)
                    rows_v = jnp.where(in_range[:, None], rows_v, 0)
                    collected = collected + jnp.sum(
                        ((rows_k != KEY_MAX) & (rows_k >= q[:, None]))
                        .astype(jnp.int32),
                        axis=-1,
                    )
                window_k.append(rows_k)
                window_v.append(rows_v)
            # the window's assembly, the kernel's operand padding and int64
            # split/join, and the answer masks; the leaf_scan kernel itself
            # is found by its name, not its scope
            with jax.named_scope("dex/lanes"):
                wk = jnp.concatenate(window_k, axis=-1)
                wv = jnp.concatenate(window_v, axis=-1)
                if use_kernel:
                    sc_k, sc_v, taken = leaf_scan(
                        wk, wv, q, cnt_s, max_count=mc, interpret=interpret
                    )
                else:
                    sc_k, sc_v, taken = leaf_scan_ref(
                        wk, wv, q, cnt_s, max_count=mc
                    )
                ok_scan = is_scan & ~shed
                sc_k = jnp.where(ok_scan[:, None], sc_k, KEY_MAX)
                sc_v = jnp.where(ok_scan[:, None], sc_v, 0)
                taken = jnp.where(
                    ok_scan, taken, jnp.where(is_scan & shed, -1, 0)
                ).astype(jnp.int32)

        # ledger: compute-side leaf search — lookups that stayed one-sided,
        # plus a scan's first (descent) hop
        with _lat_scope("price"):
            if has_lookup:
                cost = cost + (
                    live & (opc == OP_LOOKUP) & ~offl
                ).astype(jnp.float32) * obs_latency.T_LOCAL
            if has_scan:
                cost = cost + is_scan.astype(jnp.float32) * obs_latency.T_LOCAL

        # --- front-half EMA + stats ----------------------------------------
        with jax.named_scope("dex/lanes"):
            g_miss = jax.lax.psum(miss_cl, cfg.all_axes)
            g_want = jax.lax.psum(want_cl, cfg.all_axes)
            rates = g_miss / jnp.maximum(g_want, 1.0)
            new_ema = jnp.where(
                g_want[None, :, :] > 0,
                cfg.ema_decay * miss_ema
                + (1 - cfg.ema_decay) * rates[None, :, :],
                miss_ema,
            )
            f_upd = jnp.zeros((1, N_STATS), jnp.int64)
            f_upd = f_upd.at[0, STAT_OPS].set(jnp.sum(live).astype(jnp.int64))
            f_upd = f_upd.at[0, STAT_HITS].set(n_hit)
            f_upd = f_upd.at[0, STAT_FETCHES].set(n_fetch)
            f_upd = f_upd.at[0, STAT_DROPS].set(
                jnp.sum(dropped_r).astype(jnp.int64)
            )
            if has_offloadable:
                # group decisions are mesh-global: count them once, on the
                # first device
                first = (dev == 0).astype(jnp.int64)
                f_upd = f_upd.at[0, STAT_OFFLOAD_GROUPS].set(
                    first * n_off_groups
                )
                f_upd = f_upd.at[0, STAT_FETCH_GROUPS].set(
                    first * n_fetch_groups
                )
            if use_rt:
                f_upd = f_upd.at[0, STAT_RT_SKIPS].set(n_rt_skips)
                f_upd = f_upd.at[0, STAT_RT_MISPREDICTS].set(n_rt_mis)

        carry = {
            "q": q, "val": val, "opc": opc, "pr": pr, "subtree": subtree,
            "offl": offl, "gid": leaf_gid, "found": found_leaf,
            "vleaf": vals_leaf, "shed": shed, "lane": lane,
            "dropr": dropped_r, "cost": cost, "fmiss": fmiss,
        }
        if may_peek:
            carry["peek"] = peeked_leaf
        if has_scan:
            carry.update(sck=sc_k, scv=sc_v, taken=taken)
        if stamp:
            # version stamps for the back half's overlap-window check
            with jax.named_scope("dex/coherence"):
                gsafe = jnp.clip(leaf_gid, 0, n_nodes_total - 1)
                carry["vseen"] = jnp.where(live, vers[gsafe], 0)
                if has_scan and hop_gids:
                    carry["hgid"] = jnp.stack(hop_gids, axis=-1)
                    carry["hver"] = jnp.stack(hop_vers, axis=-1)
                elif has_scan:
                    carry["hgid"] = jnp.full(q.shape + (0,), -1, jnp.int64)
                    carry["hver"] = jnp.zeros(q.shape + (0,), vers.dtype)
        return carry, new_cache, new_ema, new_demand, f_upd, a_upd

    def _run_back(pool, occupancy, cache, versions, carry, b, *, check_stale):
        """Back half: overlap-window stale check (pipeline mode), the fused
        tagged request/response all_to_all pair, the conflict-resolved leaf
        apply, version bumps + cache write-through, and the reverse route
        exchange returning per-lane results."""
        n_route = cfg.n_route
        with jax.named_scope("dex/lanes"):
            vers = versions[0]
        n_nodes_total = vers.shape[0]
        q = carry["q"]
        val = carry["val"]
        opc = carry["opc"]
        pr = carry["pr"]
        subtree = carry["subtree"]
        offl = carry["offl"]
        leaf_gid = carry["gid"]
        found_leaf = carry["found"]
        vals_leaf = carry["vleaf"]
        shed = carry["shed"]
        lane = carry["lane"]
        dropped_r = carry["dropr"]
        cost = carry["cost"]
        fmiss = carry["fmiss"]
        peek_c = carry["peek"] if may_peek else None
        cap = lane.shape[1]
        with jax.named_scope("dex/lanes"):
            live = q != KEY_MAX
            is_scan = (
                live & (opc == OP_SCAN) if has_scan
                else jnp.zeros(q.shape, bool)
            )
        with jax.named_scope("dex/locate"):
            col = (subtree // s_per).astype(jnp.int32)
        if has_scan:
            sc_k, sc_v, taken = carry["sck"], carry["scv"], carry["taken"]

        # --- overlap-window stale check (pipeline back half only) ----------
        with jax.named_scope("dex/coherence"):
            n_stalls = jnp.int64(0)
            stalled = jnp.zeros(q.shape, bool)
        if check_stale:
            with jax.named_scope("dex/coherence"):
                gsafe = jnp.clip(leaf_gid, 0, n_nodes_total - 1)
                stale = live & (vers[gsafe] != carry["vseen"])
                # lookups/updates whose leaf the overlapped batch wrote
                # re-run two-sided against the authoritative post-overlap
                # pool; inserts never need forcing (the apply re-searches the
                # leaf); already-offloaded lanes are authoritative as-is
                force_off = (
                    stale & ~offl & ~shed & ~is_scan
                    & ((opc == OP_LOOKUP) | (opc == OP_UPDATE))
                ) if (has_lookup or has_update) else jnp.zeros(q.shape, bool)
                n_stalls = n_stalls + jnp.sum(force_off).astype(jnp.int64)
                if has_scan:
                    # conservative conflict stall: a scan whose window
                    # crossed any written leaf sheds to the retry lane
                    hg, hv = carry["hgid"], carry["hver"]
                    hvalid = hg >= 0
                    hsafe = jnp.clip(hg, 0, n_nodes_total - 1)
                    hstale = jnp.any(hvalid & (vers[hsafe] != hv), axis=-1)
                    sc_stale = is_scan & ~shed & (stale | hstale)
                    n_stalls = n_stalls + jnp.sum(sc_stale).astype(jnp.int64)
                    sc_k = jnp.where(sc_stale[:, None], KEY_MAX, sc_k)
                    sc_v = jnp.where(sc_stale[:, None], 0, sc_v)
                    taken = jnp.where(sc_stale, -1, taken).astype(jnp.int32)
                    shed = shed | sc_stale
                    stalled = stalled | sc_stale
                stalled = stalled | force_off
                offl_eff = offl | force_off
            with _lat_scope("stale_forced"):
                # a stale-caught lane re-resolves two-sided at the leaf: the
                # simulator's stall site prices one RPC plus a single-level
                # memory-side walk (``_offload(server, leaf, 1)``)
                cost = cost + stalled.astype(jnp.float32) * (
                    obs_latency.T_RPC + obs_latency.T_MEM
                )
        else:
            offl_eff = offl

        # --- 5. ONE fused tagged request/response all_to_all pair ----------
        with jax.named_scope("dex/lanes"):
            rstat = jnp.zeros(q.shape, jnp.int32)
            rval = jnp.zeros(q.shape, jnp.int64)
            rgid = jnp.full(q.shape, KEY_MAX, jnp.int64)
            rrow_v = jnp.zeros(q.shape + (FANOUT,), jnp.int64)
            send = jnp.zeros(q.shape, bool)
            dropped_w = jnp.zeros(q.shape, bool)
            sent_peek = jnp.zeros(q.shape, bool)
            n_off_msgs = jnp.int64(0)
            n_write_msgs = jnp.int64(0)
            n_peer_hits = jnp.int64(0)
            n_peer_misses = jnp.int64(0)
        new_pk, new_pv, new_occ = (
            pool.pool_keys, pool.pool_values, occupancy
        )
        new_cache = cache
        if do_fused:
            with jax.named_scope("dex/lanes"):
                tag = jnp.zeros(q.shape, jnp.int64)
                ok_lane = live & ~shed
                if has_lookup and may_offload:
                    tag = jnp.where(
                        ok_lane & (opc == OP_LOOKUP) & offl_eff,
                        MSG_OFF_LOOKUP, tag,
                    )
                if may_peek:
                    # a peeked leaf miss resolves two-sided at the owning
                    # column (a stale-forced lane keeps its MSG_OFF_LOOKUP
                    # instead)
                    tag = jnp.where(
                        ok_lane & (opc == OP_LOOKUP) & peek_c & ~offl_eff,
                        MSG_PEEK, tag,
                    )
                if has_update:
                    if may_offload:
                        tag = jnp.where(
                            ok_lane & (opc == OP_UPDATE) & offl_eff,
                            MSG_OFF_UPDATE, tag,
                        )
                    tag = jnp.where(
                        ok_lane & (opc == OP_UPDATE) & ~offl_eff & found_leaf,
                        MSG_UPDATE, tag,
                    )
                if has_insert:
                    if may_offload:
                        tag = jnp.where(
                            ok_lane & (opc == OP_INSERT) & offl_eff,
                            MSG_OFF_INSERT, tag,
                        )
                    tag = jnp.where(
                        ok_lane & (opc == OP_INSERT) & ~offl_eff, MSG_INSERT,
                        tag,
                    )
                if may_peek:
                    sent_peek = tag == MSG_PEEK
                send = tag != MSG_NONE
                dest = jnp.where(send, col, cfg.n_memory)
                wcap = routing.route_capacity(
                    q.shape[0], cfg.n_memory, cfg.route_capacity_factor
                )
                wpayload = jnp.stack(
                    [
                        tag,
                        jnp.where(
                            (tag == MSG_UPDATE) | (tag == MSG_INSERT)
                            | (tag == MSG_PEEK),
                            leaf_gid, KEY_MAX,
                        ),
                        subtree.astype(jnp.int64),
                        q,
                        val,
                        pr,
                    ],
                    axis=-1,
                )                                           # [Q, REQ_FIELDS]
                wbuf, wlane, dropped_w = routing.pack_by_dest(
                    wpayload, dest, cfg.n_memory, wcap
                )
                dropped_w = dropped_w & send
            with jax.named_scope("dex/fused_a2a/request"):
                req = routing.a2a(wbuf, cfg.memory_axis)  # [n_mem, wcap, RF]
            with jax.named_scope("dex/lanes"):
                if has_writes:
                    # every route-replica of this memory column must apply
                    # the identical write batch (pool replicas stay
                    # consistent)
                    req = routing.gather_route(req, cfg)  # [R, n_mem, wcap, RF]
                flat = req.reshape(-1, REQ_FIELDS)
                tagf = flat[:, 0]
                gidf = flat[:, 1]
                stf = flat[:, 2]
                kf = flat[:, 3]
                vf = flat[:, 4]
                prf = flat[:, 5]
                wgid = jnp.where(
                    (tagf == MSG_UPDATE) | (tagf == MSG_INSERT), gidf, KEY_MAX
                )
                resp_val = jnp.zeros(kf.shape, jnp.int64)
                o_found = jnp.zeros(kf.shape, bool)
                peekf = jnp.zeros(kf.shape, bool)
            if may_offload or may_peek:
                with jax.named_scope("dex/offload"):
                    offf = (
                        (tagf >= MSG_OFF_LOOKUP) & (tagf <= MSG_OFF_INSERT)
                        if may_offload else jnp.zeros(kf.shape, bool)
                    )
                    if may_peek:
                        peekf = tagf == MSG_PEEK
                    walkf = offf | peekf
                    # owner-side block walk for offloaded (and peer-missed
                    # peeked) lanes (§6): the whole remaining traversal runs
                    # next to the data
                    stl = jnp.where(walkf, stf % s_per, 0).astype(jnp.int32)
                    loc = jnp.zeros(kf.shape, jnp.int32)
                    for _ in range(levels - 1):
                        rows = node_rows(pool.pool_keys, stl, loc)
                        cnt = jnp.sum(rows <= kf[:, None], axis=-1)
                        slot = jnp.maximum(cnt - 1, 0).astype(jnp.int32)
                        loc = pool.pool_children[stl, loc * FANOUT + slot]
                    o_rows_k = node_rows(pool.pool_keys, stl, loc)
                    o_eq = o_rows_k == kf[:, None]
                    o_found = jnp.any(o_eq, axis=-1) & walkf
                    o_val = jnp.sum(
                        jnp.where(
                            o_eq, node_rows(pool.pool_values, stl, loc), 0
                        ),
                        axis=-1,
                    )
                    if may_offload:
                        gid_eff = meta.node_gid(stf, loc.astype(jnp.int64))
                        wgid = jnp.where(
                            (tagf == MSG_OFF_UPDATE)
                            | (tagf == MSG_OFF_INSERT),
                            gid_eff, wgid,
                        )
                    peer_hit = jnp.zeros(kf.shape, bool)
                    if may_peek:
                        # sibling-cache overlay: if this chip's own cache
                        # holds a version-fresh copy of the peeked leaf,
                        # answer from it — a stale or absent row falls back
                        # to the walk above
                        peer_hit, p_found, p_val = fleet_cache.peer_answer(
                            cache, cfg, vers, gidf, kf, peekf
                        )
                        o_found = jnp.where(peer_hit, p_found, o_found)
                        o_val = jnp.where(peer_hit, p_val, o_val)
                    lk_tags = (
                        (tagf == MSG_OFF_LOOKUP) | peekf
                        if may_offload else peekf
                    )
                    resp_val = jnp.where(lk_tags, o_val, 0)
            if has_writes:
                with jax.named_scope("dex/lanes"):
                    allow_ins = tagf == MSG_INSERT
                    if may_offload:
                        allow_ins = allow_ins | (tagf == MSG_OFF_INSERT)
                with jax.named_scope("dex/apply"):
                    (new_pk, new_pv, new_occ, wstat, rows_v_all,
                     ins_in_leaf) = _apply_leaf_writes(
                        pool.pool_keys, pool.pool_values, occupancy, meta,
                        cfg, wgid, kf, vf, prf, allow_ins,
                        use_kernel=use_kernel, interpret=interpret,
                    )
            with jax.named_scope("dex/lanes"):
                if not has_writes:
                    wstat = jnp.zeros(kf.shape, jnp.int32)
                    rows_v_all = jnp.zeros(kf.shape + (FANOUT,), jnp.int64)
                    ins_in_leaf = jnp.zeros(kf.shape, bool)
                if may_offload or may_peek:
                    wstat = jnp.where(
                        lk_tags,
                        jnp.where(o_found, STATUS_OK, STATUS_MISS),
                        wstat,
                    )
                # field 3 doubles as the peer-cache-hit bit for MSG_PEEK
                # lanes (they are lookups, so the insert-path consumers never
                # read it)
                ins_flag = (
                    jnp.where(peekf, peer_hit, ins_in_leaf)
                    if may_peek else ins_in_leaf
                )
                resp = jnp.concatenate(
                    [
                        wstat[:, None].astype(jnp.int64),
                        resp_val[:, None],
                        wgid[:, None],
                        ins_flag[:, None].astype(jnp.int64),
                        rows_v_all,
                    ],
                    axis=-1,
                )
                if has_writes:
                    # respond only to this device's own route row
                    r_lin = routing.route_linear_index(cfg, mesh)
                    resp = jnp.take(
                        resp.reshape(
                            cfg.n_route, cfg.n_memory, wcap,
                            RESP_HEAD + FANOUT,
                        ),
                        r_lin, axis=0,
                    )
                else:
                    resp = resp.reshape(cfg.n_memory, wcap, RESP_HEAD + FANOUT)
            with jax.named_scope("dex/fused_a2a/response"):
                resp = routing.a2a(resp, cfg.memory_axis)
            with jax.named_scope("dex/lanes"):
                back = routing.unpack_to_lanes(resp, wlane, q.shape[0], 0)
                rstat = back[..., 0].astype(jnp.int32)
                rval = back[..., 1]
                rgid = back[..., 2]
                r_ins = back[..., 3] != 0
                rrow_v = back[..., RESP_HEAD:]
                delivered = send & ~dropped_w
                is_off_lane = offl_eff & send
                n_off_msgs = jnp.sum(delivered & is_off_lane).astype(jnp.int64)
                n_write_msgs = jnp.sum(
                    delivered & ~is_off_lane & (opc != OP_LOOKUP)
                ).astype(jnp.int64)
                if may_peek:
                    n_peer_hits = jnp.sum(
                        delivered & sent_peek & r_ins
                    ).astype(jnp.int64)
                    n_peer_misses = jnp.sum(
                        delivered & sent_peek & ~r_ins
                    ).astype(jnp.int64)

        # --- 6. write-through-and-invalidate + version bump ----------------
        new_versions = versions
        if has_writes:
            with jax.named_scope("dex/coherence"):
                delivered = send & ~dropped_w
                wrote_ok = (
                    delivered
                    & ((opc == OP_UPDATE) | (opc == OP_INSERT))
                    & (rstat == STATUS_OK)
                )
                gsafe0 = jnp.where(wrote_ok, rgid, 0)
                nv = vers[gsafe0] + 1
                gsafe = jnp.where(wrote_ok, rgid, n_nodes_total)
                vers2 = vers.at[gsafe].max(nv, mode="drop")
                new_versions = jax.lax.pmax(vers2[None, :], cfg.all_axes)
                set_idx = (
                    routing.hash64(rgid) % jnp.uint64(cfg.cache_sets)
                ).astype(jnp.int32)
                eqt = new_cache.tags[0, set_idx] == rgid[:, None]
                chit = jnp.any(eqt, axis=-1) & wrote_ok
                way = jnp.argmax(eqt, axis=-1).astype(jnp.int32)
                if has_update:
                    # refresh the chip's own cached row with the
                    # authoritative post-batch values, stamped with the
                    # bumped version — but NOT when the leaf also took
                    # same-batch inserts (possibly from another chip): the
                    # cached keys plane would be stale under a current
                    # version stamp; leaving the old stamp makes the version
                    # check refetch the whole row instead
                    u_hit = chit & (opc == OP_UPDATE) & ~r_ins
                    if check_stale:
                        # a stale-forced update resolved two-sided against a
                        # leaf the overlapped batch moved: the chip's cached
                        # keys plane is one batch behind the response's
                        # value row, so an in-place refresh would stitch a
                        # misaligned pair under a current version stamp.
                        # Leave the old stamp; the bumped version forces a
                        # clean refetch.
                        u_hit = u_hit & ~force_off
                    sidx = jnp.where(u_hit, set_idx, cfg.cache_sets)
                    cvals = new_cache.values.at[0, sidx, way].set(
                        rrow_v, mode="drop"
                    )
                    cver = new_cache.ver.at[0, sidx, way].set(
                        jnp.where(u_hit, nv, 0), mode="drop"
                    )
                    new_cache = new_cache._replace(values=cvals, ver=cver)
                if has_insert:
                    # drop the chip's own (now key-shifted) cached row
                    i_hit = chit & (opc == OP_INSERT)
                    sidx = jnp.where(i_hit, set_idx, cfg.cache_sets)
                    ctags = new_cache.tags.at[0, sidx, way].set(
                        -1, mode="drop"
                    )
                    new_cache = new_cache._replace(tags=ctags)

        # --- 7. per-lane results + statuses --------------------------------
        with jax.named_scope("dex/lanes"):
            out_found = jnp.zeros(q.shape, bool)
            out_val = jnp.zeros(q.shape, jnp.int64)
            if has_lookup:
                is_lk = live & (opc == OP_LOOKUP)
                # a lane resolved two-sided (offloaded, stale-forced, or
                # peeked) takes the owning column's answer; the rest keep the
                # local cached-descent result
                two_sided = (offl_eff | sent_peek) if may_peek else offl_eff
                out_found = jnp.where(
                    two_sided,
                    (rstat == STATUS_OK) & send & ~dropped_w,
                    found_leaf & ~shed,
                ) & is_lk
                out_val = jnp.where(
                    out_found, jnp.where(two_sided, rval, vals_leaf), 0
                )
            status = jnp.full(q.shape, STATUS_MISS, jnp.int32)
            if has_writes:
                is_w = live & ((opc == OP_UPDATE) | (opc == OP_INSERT))
                shed_w = is_w & (shed | dropped_w)
                status = jnp.where(
                    is_w & send & ~dropped_w & ~shed,
                    rstat,
                    jnp.where(shed_w, STATUS_SHED, STATUS_MISS),
                )
            lane_shed = shed | (send & dropped_w)

        # --- 7b. per-lane back-half pricing + latency histogram ------------
        # (obs/latency.py).  Two-sided trips price the simulator's offload
        # rule (one RPC + the owner's per-level memory-side walk); peer
        # peeks the sibling's cached access (hit) or a one-level owner walk
        # (miss); fetched-path writes one write-through WRITE — suppressed
        # in pipelined mode, where the write rides the overlapped fused
        # round off the critical path (the simulator's pipeline_overlap
        # rule).  Each live routed lane then bins into exactly one
        # (op class, outcome path, bucket) cell — a pure per-device
        # scatter, so the plane adds zero collectives.
        with _lat_scope("offload"):
            delivered_l = send & ~dropped_w
            is_off = offl_eff & send
            off_norm = delivered_l & is_off & ~stalled
            cost = cost + off_norm.astype(jnp.float32) * (
                obs_latency.T_RPC + float(levels) * obs_latency.T_MEM
            )
        if may_peek:
            with _lat_scope("peer_peek"):
                pk = delivered_l & sent_peek
                cost = cost + pk.astype(jnp.float32) * (
                    obs_latency.T_RPC + jnp.where(
                        r_ins, obs_latency.T_CACHED, obs_latency.T_MEM
                    )
                )
        if has_writes and not check_stale:
            with _lat_scope("write_through"):
                wl = delivered_l & ~is_off & (
                    (opc == OP_UPDATE) | (opc == OP_INSERT)
                )
                cost = cost + wl.astype(jnp.float32) * obs_latency.T_WRITE
        with _lat_scope("bin"):
            path = jnp.zeros(q.shape, jnp.int32)             # cache_hit
            path = jnp.where(fmiss, 1, path)                 # remote_fetch
            if may_peek:
                path = jnp.where(delivered_l & sent_peek, 2, path)
            path = jnp.where(delivered_l & is_off & ~stalled, 3, path)
            path = jnp.where(lane_shed, 5, path)             # shed
            if check_stale:
                path = jnp.where(stalled, 4, path)           # stale_forced
            cls = jnp.clip(opc, 0, obs_latency.N_CLASSES - 1)
            bkt = obs_latency.bucket_index(cost, xp=jnp)
            h_upd = jnp.zeros(
                (obs_latency.N_CLASSES, obs_latency.N_PATHS,
                 obs_latency.N_BUCKETS),
                jnp.int64,
            ).at[cls, path, bkt].add(live.astype(jnp.int64))

        # --- 8. back-half stats --------------------------------------------
        with jax.named_scope("dex/lanes"):
            n_shed = jnp.sum(lane_shed & live).astype(jnp.int64)
            b_upd = jnp.zeros((1, N_STATS), jnp.int64)
            b_upd = b_upd.at[0, STAT_OFFLOADS].set(n_off_msgs)
            b_upd = b_upd.at[0, STAT_WRITES].set(n_write_msgs)
            b_upd = b_upd.at[0, STAT_DROPS].set(n_shed)
            b_upd = b_upd.at[0, STAT_SPLITS].set(
                jnp.sum(status == STATUS_SPLIT).astype(jnp.int64)
            )
            b_upd = b_upd.at[0, STAT_PIPE_STALLS].set(n_stalls)
            b_upd = b_upd.at[0, STAT_PEER_HITS].set(n_peer_hits)
            b_upd = b_upd.at[0, STAT_PEER_MISSES].set(n_peer_misses)

        # --- 9. results back to the requesting lanes ------------------------
        with jax.named_scope("dex/lanes"):
            fields = [
                out_found.astype(jnp.int64)[:, None],
                out_val[:, None],
                status.astype(jnp.int64)[:, None],
                lane_shed.astype(jnp.int64)[:, None],
            ]
            if has_scan:
                fields += [taken.astype(jnp.int64)[:, None], sc_k, sc_v]
            resp_b = jnp.concatenate(fields, axis=-1)
            width = resp_b.shape[-1]
            resp_b = resp_b.reshape(n_route, cap, width)
        with jax.named_scope("dex/route_back"):
            back_b = routing.route_exchange(resp_b, cfg, mesh, reverse=True)
        with jax.named_scope("dex/lanes"):
            out = routing.unpack_to_lanes(back_b, lane, b, 0)
            res_found = (out[..., 0] != 0) & ~dropped_r
            res_val = jnp.where(dropped_r, 0, out[..., 1])
            res_status = jnp.where(
                dropped_r, STATUS_SHED, out[..., 2].astype(jnp.int32)
            )
            if not has_writes:
                res_status = jnp.where(
                    dropped_r & (q.shape[0] > 0), STATUS_SHED, STATUS_MISS
                ).astype(jnp.int32)
            res_shed = (out[..., 3] != 0) | dropped_r
            lane_out = [res_found, res_val, res_status, res_shed]
            if has_scan:
                res_taken = jnp.where(
                    dropped_r, -1, out[..., 4]
                ).astype(jnp.int32)
                res_k = jnp.where(
                    dropped_r[:, None], KEY_MAX, out[..., 5 : 5 + mc]
                )
                res_v = jnp.where(
                    dropped_r[:, None], 0, out[..., 5 + mc : 5 + 2 * mc]
                )
                lane_out += [res_k, res_v, res_taken]
        return (new_pk, new_pv, new_occ, new_versions, new_cache, b_upd,
                h_upd, lane_out)

    def _accumulate(stats, f_upd, b_upd, lat_hist, h_upd, lat_audit, a_upd):
        """The step's counter, histogram and audit increments."""
        with jax.named_scope("dex/lanes"):
            new_stats = stats + f_upd + b_upd
        with _lat_scope("bin"):
            new_hist = lat_hist + h_upd[None]
        with _lat_scope("audit"):
            new_audit = lat_audit + a_upd[None]
        return new_stats, new_hist, new_audit

    def local_fn(pool, occupancy, cache, boundaries, miss_ema, stats, demand,
                 versions, succ, lat_hist, lat_audit, rtk, rth, rts, rtl,
                 rtv, opcodes, keys, values):
        b = keys.shape[0]
        carry, new_cache, new_ema, new_demand, f_upd, a_upd = _run_front(
            pool, cache, boundaries, miss_ema, stats, demand, versions, succ,
            rtk, rth, rts, rtl, rtv, opcodes, keys, values, stamp=False,
        )
        (new_pk, new_pv, new_occ, new_versions, new_cache, b_upd, h_upd,
         lane_out) = _run_back(
            pool, occupancy, new_cache, versions, carry, b, check_stale=False,
        )
        new_stats, new_hist, new_audit = _accumulate(
            stats, f_upd, b_upd, lat_hist, h_upd, lat_audit, a_upd
        )
        outs = [new_cache, new_ema, new_stats, new_demand, new_hist,
                new_audit] + lane_out
        if has_writes:
            outs = [new_pk, new_pv, new_occ, new_versions] + outs
        return tuple(outs)

    def local_pipe(pool, occupancy, cache, boundaries, miss_ema, stats,
                   demand, versions, succ, lat_hist, lat_audit, rtk, rth,
                   rts, rtl, rtv, carry_in, opcodes, keys, values):
        # one pipeline step: the NEW batch's front half next to the CARRIED
        # batch's back half.  The back half probes the cache as returned by
        # this step's front (an elementwise composition — the two halves
        # share no collective data dependency, so XLA is free to overlap
        # the back half's all_to_all with the front half's fetch rounds).
        b = keys.shape[0]
        with jax.named_scope("pipe/front"), routing.trace_phase("pipe/front"):
            carry_out, cache_f, new_ema, new_demand, f_upd, a_upd = _run_front(
                pool, cache, boundaries, miss_ema, stats, demand, versions,
                succ, rtk, rth, rts, rtl, rtv, opcodes, keys, values,
                stamp=True,
            )
        carried = dict(zip(carry_keys, carry_in))
        with jax.named_scope("pipe/back"), routing.trace_phase("pipe/back"):
            (new_pk, new_pv, new_occ, new_versions, new_cache, b_upd, h_upd,
             lane_out) = _run_back(
                pool, occupancy, cache_f, versions, carried, b,
                check_stale=True,
            )
        # the histogram lags STAT_OPS by one batch here (a lane bins when
        # its back half lands); the drain step closes the gap exactly
        new_stats, new_hist, new_audit = _accumulate(
            stats, f_upd, b_upd, lat_hist, h_upd, lat_audit, a_upd
        )
        outs = [new_cache, new_ema, new_stats, new_demand, new_hist,
                new_audit]
        outs += [carry_out[k] for k in carry_keys]
        outs += lane_out
        if has_writes:
            outs = [new_pk, new_pv, new_occ, new_versions] + outs
        return tuple(outs)

    dev_spec = P(cfg.all_axes)
    pool_in = pool_specs(cfg.memory_axis)
    cache_specs = DexCache(
        tags=dev_spec, keys=dev_spec, children=dev_spec, values=dev_spec,
        fifo=dev_spec, ver=dev_spec,
    )
    mem = P(cfg.memory_axis)
    words = pool_in.pool_keys
    lanes = P(cfg.all_axes)

    plan = {
        "route_rounds": 1,
        "fused_pairs": 1 if do_fused else 0,
        "descent_levels": (levels if do_leaf else levels - 1)
        if do_descent else 0,
        "scan_hops": hops,
        "pipeline": bool(pipeline),
        # the jax.named_scope families that label every op of the step in a
        # profiler trace (the first ``dex/`` component of an op_name; no
        # family nests in another); metadata only — they add no ops and no
        # collectives
        "phases": ("dex/route", "dex/descent", "dex/scan", "dex/fused_a2a",
                   "dex/apply", "dex/lat", "dex/route_back", "dex/locate",
                   "dex/offload", "dex/coherence", "dex/lanes"),
    }

    enabled_codes = [
        code for flag, code in [
            (has_lookup, OP_LOOKUP), (has_update, OP_UPDATE),
            (has_insert, OP_INSERT), (has_scan, OP_SCAN),
        ] if flag
    ]

    def mask_opcodes(opcodes, keys, values):
        # opcodes outside the static ``ops`` set are true no-ops: their
        # keys are masked before routing, so they consume no bucket
        # capacity, mint no demand/stats and return inactive results
        with jax.named_scope("dex/lanes"):
            opcodes = opcodes.astype(jnp.int32)
            keys = keys.astype(jnp.int64)
            allowed = jnp.zeros(opcodes.shape, bool)
            for code in enabled_codes:
                allowed = allowed | (opcodes == code)
            keys = jnp.where(allowed, keys, KEY_MAX)
            return opcodes, keys, values.astype(jnp.int64)

    if not pipeline:
        sharded = routing.shard_map_compat(
            local_fn,
            mesh=mesh,
            in_specs=(pool_in, mem, cache_specs, P(), dev_spec, dev_spec,
                      dev_spec, dev_spec, dev_spec, dev_spec, dev_spec,
                      P(), P(), P(), P(), P(),
                      lanes, lanes, lanes),
            out_specs=tuple(
                ([words, words, mem, dev_spec] if has_writes else [])
                + [cache_specs, dev_spec, dev_spec, dev_spec, dev_spec,
                   dev_spec, lanes, lanes, lanes, lanes]
                + ([lanes, lanes, lanes] if has_scan else [])
            ),
        )

        def engine(state: DexState, opcodes: jax.Array, keys: jax.Array,
                   values: jax.Array):
            if keys.shape[0] == 0:
                return state, _empty_result(0, mc, has_scan)
            opcodes, keys, values = mask_opcodes(opcodes, keys, values)
            res = sharded(
                state.pool, state.occupancy, state.cache, state.boundaries,
                state.miss_ema, state.stats, state.route_demand,
                state.versions, state.succ, state.lat_hist, state.lat_audit,
                state.rt_keys, state.rt_hi, state.rt_sub, state.rt_local,
                state.rt_ver, opcodes, keys, values,
            )
            res = list(res)
            new_state = state
            if has_writes:
                new_pk, new_pv, new_occ, new_versions = res[:4]
                res = res[4:]
                new_state = new_state._replace(
                    pool=state.pool._replace(
                        pool_keys=new_pk, pool_values=new_pv
                    ),
                    occupancy=new_occ,
                    versions=new_versions,
                )
            new_cache, new_ema, new_stats, new_demand, new_hist, new_audit = (
                res[:6]
            )
            found, vals, status, shed = res[6:10]
            new_state = new_state._replace(
                cache=new_cache, miss_ema=new_ema, stats=new_stats,
                route_demand=new_demand, lat_hist=new_hist,
                lat_audit=new_audit,
            )
            result = EngineResult(found=found, values=vals, status=status,
                                  shed=shed)
            if has_scan:
                sk, sv, tk = res[10:13]
                result = result._replace(
                    scan_keys=sk, scan_values=sv, taken=tk
                )
            return new_state, result

        engine.plan = plan
        return engine

    # ---- pipeline=True: the fused two-stage step + host-side driver -------
    carry_specs = tuple(lanes for _ in carry_keys)
    sharded_pipe = routing.shard_map_compat(
        local_pipe,
        mesh=mesh,
        in_specs=(pool_in, mem, cache_specs, P(), dev_spec, dev_spec,
                  dev_spec, dev_spec, dev_spec, dev_spec, dev_spec,
                  P(), P(), P(), P(), P(),
                  carry_specs, lanes, lanes, lanes),
        out_specs=tuple(
            ([words, words, mem, dev_spec] if has_writes else [])
            + [cache_specs, dev_spec, dev_spec, dev_spec, dev_spec, dev_spec]
            + list(carry_specs)
            + [lanes, lanes, lanes, lanes]
            + ([lanes, lanes, lanes] if has_scan else [])
        ),
    )

    lane_sharding = NamedSharding(mesh, lanes)

    def init_carry(b_global: int):
        """The all-inactive prologue carry for a global batch width: every
        routed slot holds the KEY_MAX sentinel, so the first step's back
        half is a structural no-op (no sends, no writes, no results)."""
        n_dev = cfg.n_devices
        if b_global % n_dev:
            raise ValueError(
                f"batch width {b_global} must divide over {n_dev} devices"
            )
        b_loc = b_global // n_dev
        cap0 = routing.route_capacity(
            b_loc, cfg.n_route, cfg.route_capacity_factor
        )
        q_g = n_dev * cfg.n_route * cap0
        h = max(hops - 1, 0)
        carry = {
            "q": jnp.full((q_g,), KEY_MAX, jnp.int64),
            "val": jnp.zeros((q_g,), jnp.int64),
            "opc": jnp.zeros((q_g,), jnp.int32),
            "pr": jnp.zeros((q_g,), jnp.int64),
            "subtree": jnp.zeros((q_g,), jnp.int32),
            "offl": jnp.zeros((q_g,), bool),
            "gid": jnp.zeros((q_g,), jnp.int64),
            "found": jnp.zeros((q_g,), bool),
            "vleaf": jnp.zeros((q_g,), jnp.int64),
            "shed": jnp.zeros((q_g,), bool),
            "vseen": jnp.zeros((q_g,), jnp.int32),
            "lane": jnp.zeros((n_dev * cfg.n_route, cap0), jnp.int32),
            "dropr": jnp.zeros((b_global,), bool),
            "cost": jnp.zeros((q_g,), jnp.float32),
            "fmiss": jnp.zeros((q_g,), bool),
        }
        if may_peek:
            carry["peek"] = jnp.zeros((q_g,), bool)
        if has_scan:
            carry.update(
                sck=jnp.full((q_g, mc), KEY_MAX, jnp.int64),
                scv=jnp.zeros((q_g, mc), jnp.int64),
                taken=jnp.zeros((q_g,), jnp.int32),
                hgid=jnp.full((q_g, h), -1, jnp.int64),
                hver=jnp.zeros((q_g, h), jnp.int32),
            )
        return tuple(
            jax.device_put(carry[k], lane_sharding) for k in carry_keys
        )

    def pipe_step(state: DexState, carry, opcodes, keys, values):
        opcodes, keys, values = mask_opcodes(opcodes, keys, values)
        res = sharded_pipe(
            state.pool, state.occupancy, state.cache, state.boundaries,
            state.miss_ema, state.stats, state.route_demand, state.versions,
            state.succ, state.lat_hist, state.lat_audit, state.rt_keys,
            state.rt_hi, state.rt_sub, state.rt_local, state.rt_ver,
            tuple(carry), opcodes, keys, values,
        )
        res = list(res)
        new_state = state
        if has_writes:
            new_pk, new_pv, new_occ, new_versions = res[:4]
            res = res[4:]
            new_state = new_state._replace(
                pool=state.pool._replace(pool_keys=new_pk, pool_values=new_pv),
                occupancy=new_occ,
                versions=new_versions,
            )
        new_cache, new_ema, new_stats, new_demand, new_hist, new_audit = (
            res[:6]
        )
        res = res[6:]
        new_state = new_state._replace(
            cache=new_cache, miss_ema=new_ema, stats=new_stats,
            route_demand=new_demand, lat_hist=new_hist, lat_audit=new_audit,
        )
        carry_out = tuple(res[: len(carry_keys)])
        res = res[len(carry_keys):]
        found, vals, status, shed = res[:4]
        result = EngineResult(found=found, values=vals, status=status,
                              shed=shed)
        if has_scan:
            sk, sv, tk = res[4:7]
            result = result._replace(scan_keys=sk, scan_values=sv, taken=tk)
        return new_state, carry_out, result

    plan = dict(plan)
    plan.update(
        pipeline=True,
        stages=("front", "back"),
        overlap_phases=("pipe/front", "pipe/back"),
    )
    return EnginePipeline(pipe_step, init_carry, plan)
