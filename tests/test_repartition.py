"""Tests for the fixed ``LogicalPartitions.rebalance`` edge cases and the
live repartition controller (core/repartition.py).

Multi-device behaviour (drop reduction + round-trip result parity on the
8-device mesh) lives in tests/mesh_check.py; everything here runs on a
single device.
"""

import numpy as np

from repro.core import dex as dex_mod
from repro.core import pool as pool_mod
from repro.core.nodes import KEY_MAX, KEY_MIN
from repro.core.partition import LogicalPartitions
from repro.core.repartition import (
    RepartitionConfig,
    RepartitionController,
    install_boundaries,
    moved_intervals,
    node_key_ranges,
)


# ---------------------------------------------------------------------------
# rebalance edge cases (the bugs this PR fixes)
# ---------------------------------------------------------------------------


class TestRebalanceEdgeCases:
    def test_heavy_skew_stays_in_data_hull(self):
        """Skewed loads must not emit boundaries in the int64 sentinel
        space (the old walk priced the KEY_MIN/KEY_MAX edge widths as
        populated and produced boundaries like -6.8e18 that own no real
        keys)."""
        p = LogicalPartitions.equal_width(4, 0, 1000)
        p2 = p.rebalance([100.0, 1.0, 1.0, 1.0])
        inner = p2.boundaries[1:-1]
        assert p2.num_partitions == 4
        assert (inner > -1000).all() and (inner < 2000).all()
        # with an explicit sampled key range the hull is exact
        p3 = p.rebalance([100.0, 1.0, 1.0, 1.0], key_range=(0, 999))
        assert (p3.boundaries[1:-1] >= 0).all()
        assert (p3.boundaries[1:-1] <= 999).all()

    def test_zero_load_preserves_partition_count(self):
        p = LogicalPartitions.equal_width(4, 0, 1000)
        p2 = p.rebalance([0.0, 0.0, 0.0, 0.0])
        # no signal: table unchanged, never collapsed 4 -> 1
        assert p2.num_partitions == 4
        np.testing.assert_array_equal(p2.boundaries, p.boundaries)

    def test_partial_zero_loads_preserve_partition_count(self):
        p = LogicalPartitions.equal_width(4, 0, 1000)
        p2 = p.rebalance([10.0, 0.0, 0.0, 0.0], key_range=(0, 999))
        assert p2.num_partitions == 4
        assert np.all(np.diff(p2.boundaries.astype(object)) > 0)

    def test_single_hot_partition_converges(self):
        """Iterated measure->rebalance must concentrate boundaries around a
        single hot range until the load spreads over all partitions."""
        parts = LogicalPartitions.equal_width(4, 0, 100_000)
        hot = np.arange(40_000, 50_000)
        for _ in range(6):
            loads = np.bincount(parts.owner_of(hot), minlength=4)
            parts = parts.rebalance(loads, key_range=(0, 99_999))
            assert parts.num_partitions == 4
        final = np.bincount(parts.owner_of(hot), minlength=4)
        assert final.max() < 0.3 * hot.size  # near-equal split of the range

    def test_equal_width_narrow_range_preserves_count(self):
        p = LogicalPartitions.equal_width(4, 0, 2)
        assert p.num_partitions == 4
        assert np.unique(p.boundaries).size == 5

    def test_from_samples_few_distinct_preserves_count(self):
        p = LogicalPartitions.from_samples(np.array([7, 7, 7, 7, 7]), 4)
        assert p.num_partitions == 4
        assert np.unique(p.boundaries).size == 5

    def test_single_partition_is_noop(self):
        p = LogicalPartitions(np.array([KEY_MIN, KEY_MAX], np.int64))
        p2 = p.rebalance([42.0])
        assert p2.num_partitions == 1


# ---------------------------------------------------------------------------
# controller primitives
# ---------------------------------------------------------------------------


def _small_state(n_route=2, n_memory=1, n_keys=2000):
    keys = np.arange(1, n_keys + 1, dtype=np.int64) * 10
    pool, meta = pool_mod.build_pool(keys, keys * 3, level_m=1, fill=0.7,
                                     n_shards=n_memory)
    cfg = dex_mod.DexMeshConfig(n_route=n_route, n_memory=n_memory)
    mid = int(keys[n_keys // 2])
    bounds = np.array([KEY_MIN, mid, KEY_MAX], np.int64)
    state = dex_mod.init_state(pool, meta, cfg, bounds)
    return keys, pool, meta, cfg, state, bounds


class TestNodeKeyRanges:
    def test_ranges_tile_each_level(self):
        keys, pool, meta, _, _, _ = _small_state()
        gids, lo, hi = node_key_ranges(pool_mod.keys_i64(pool), meta)
        assert (hi.astype(object) > lo.astype(object)).all()
        # leaves alone must tile [KEY_MIN, KEY_MAX) exactly once
        is_leaf = (gids % meta.subtree_cap) >= meta.leaf_start
        llo = np.sort(lo[is_leaf].astype(object))
        lhi = np.sort(hi[is_leaf].astype(object))
        assert llo[0] == KEY_MIN and lhi[-1] == KEY_MAX
        np.testing.assert_array_equal(llo[1:], lhi[:-1])

    def test_every_key_covered_by_one_leaf(self):
        keys, pool, meta, _, _, _ = _small_state()
        gids, lo, hi = node_key_ranges(pool_mod.keys_i64(pool), meta)
        is_leaf = (gids % meta.subtree_cap) >= meta.leaf_start
        lo_l, hi_l = lo[is_leaf], hi[is_leaf]
        probe = keys[:: 97]
        covered = (
            (lo_l[None, :].astype(object) <= probe[:, None])
            & (probe[:, None] < hi_l[None, :].astype(object))
        ).sum(axis=1)
        assert (covered == 1).all()


class TestMovedIntervals:
    def test_disjoint_and_exact(self):
        old = LogicalPartitions(np.array([KEY_MIN, 100, 200, KEY_MAX],
                                         np.int64))
        new = LogicalPartitions(np.array([KEY_MIN, 150, 200, KEY_MAX],
                                         np.int64))
        assert moved_intervals(old, new) == [(100, 150)]
        assert moved_intervals(old, old) == []

    def test_full_shift(self):
        old = LogicalPartitions(np.array([KEY_MIN, 100, KEY_MAX], np.int64))
        new = LogicalPartitions(np.array([KEY_MIN, 500, KEY_MAX], np.int64))
        assert moved_intervals(old, new) == [(100, 500)]


class TestInstallBoundaries:
    def test_bumps_only_moved_nodes(self):
        keys, pool, meta, cfg, state, bounds = _small_state()
        old = LogicalPartitions(bounds)
        new = old.rebalance([3.0, 1.0], key_range=(int(keys[0]),
                                                   int(keys[-1])))
        st2, n_inval, _, _ = install_boundaries(state, meta, old, new)
        assert n_inval > 0
        v = np.asarray(st2.versions)
        assert int((v > 0).sum()) == n_inval * v.shape[0]
        np.testing.assert_array_equal(
            np.asarray(st2.boundaries), new.boundaries
        )
        # nodes outside the moved interval keep version 0
        gids, lo, hi = node_key_ranges(pool_mod.keys_i64(pool), meta)
        (a, b), = moved_intervals(old, new)
        untouched = gids[(hi.astype(object) <= a) | (lo.astype(object) >= b)]
        assert (v[0, untouched] == 0).all()

    def test_noop_install_invalidates_nothing(self):
        _, _, meta, _, state, bounds = _small_state()
        old = LogicalPartitions(bounds)
        st2, n_inval, sb, sa = install_boundaries(state, meta, old, old)
        assert n_inval == 0 and sb == sa
        assert int(np.asarray(st2.versions).sum()) == 0


class TestController:
    def _stats(self, served, drops=0, n_memory=1):
        n_route = len(served)
        s = np.zeros((n_route * n_memory, dex_mod.N_STATS), np.int64)
        s[:, dex_mod.STAT_OPS] = np.repeat(served, n_memory)
        s[0, dex_mod.STAT_DROPS] = drops
        return s

    def test_trigger_needs_min_ops(self):
        parts = LogicalPartitions.equal_width(2, 0, 1000)
        ctl = RepartitionController(
            parts, n_memory=1,
            cfg=RepartitionConfig(imbalance_threshold=1.25, min_ops=1000),
        )
        ctl.observe(self._stats([400, 10]))
        assert not ctl.should_repartition()     # 410 ops < min_ops
        ctl.observe(self._stats([1200, 30]))    # cumulative counters
        assert ctl.should_repartition()

    def test_drop_fraction_triggers(self):
        parts = LogicalPartitions.equal_width(2, 0, 1000)
        ctl = RepartitionController(
            parts, n_memory=1,
            cfg=RepartitionConfig(imbalance_threshold=10.0, drop_frac=0.01,
                                  min_ops=100),
        )
        ctl.observe(self._stats([300, 290], drops=50))
        assert ctl.should_repartition()

    def test_balanced_load_never_triggers(self):
        parts = LogicalPartitions.equal_width(2, 0, 1000)
        ctl = RepartitionController(
            parts, n_memory=1,
            cfg=RepartitionConfig(imbalance_threshold=1.25, min_ops=100),
        )
        ctl.observe(self._stats([500, 500]))
        assert not ctl.should_repartition()

    def test_demand_signal_preferred_and_hull_tracked(self):
        parts = LogicalPartitions.equal_width(2, 0, 1000)
        ctl = RepartitionController(
            parts, n_memory=1,
            cfg=RepartitionConfig(imbalance_threshold=1.25, min_ops=100),
        )
        demand = np.array([[900, 0], [0, 100]], np.int64)
        keys = np.array([5, 400, 800, KEY_MAX], np.int64)
        ctl.observe(self._stats([100, 100]), keys, demand=demand)
        assert ctl.should_repartition()          # demand sees past the cap
        prop = ctl.propose()
        assert prop.num_partitions == 2
        assert 5 <= int(prop.boundaries[1]) <= 800   # hull from keys

    def test_maybe_repartition_installs_and_cools_down(self):
        keys, pool, meta, cfg, state, bounds = _small_state()
        ctl = RepartitionController(
            LogicalPartitions(bounds), n_memory=1,
            cfg=RepartitionConfig(imbalance_threshold=1.25, min_ops=100,
                                  cooldown_batches=2),
        )
        demand = np.array([[950, 0], [0, 50]], np.int64)
        ctl.observe(self._stats([500, 50], n_memory=1), keys, demand=demand)
        state2, report = ctl.maybe_repartition(state, meta)
        assert report is not None
        assert report.nodes_invalidated > 0
        assert LogicalPartitions(report.new_boundaries).num_partitions == 2
        np.testing.assert_array_equal(
            np.asarray(state2.boundaries), ctl.parts.boundaries
        )
        # cooldown: the next observe cannot immediately re-trigger
        ctl.observe(self._stats([500, 50]), keys,
                    demand=demand + demand)
        assert not ctl.should_repartition()
