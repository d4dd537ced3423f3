"""Incremental ClusterTensors (ISSUE 2 tentpole part 2): the
dirty-node delta path must be bit-identical to a fresh build after any
sequence of node add / drain / resource-change / status / delete, and
the cache must actually serve hits and deltas instead of full rebuilds.
"""

import numpy as np
import numpy.testing as npt
import pytest

from nomad_tpu import mock
from nomad_tpu.state.store import StateStore
from nomad_tpu.tensors.schema import (
    ClusterTensors,
    IncrementalClusterCache,
)


def assert_cluster_equal(got: ClusterTensors, want: ClusterTensors):
    assert got.n_real == want.n_real
    assert got.n_pad == want.n_pad
    for f in ClusterTensors._PLANE_FIELDS:
        npt.assert_array_equal(getattr(got, f), getattr(want, f),
                               err_msg=f)
    for f in ClusterTensors._RAGGED_FIELDS:
        assert getattr(got, f) == getattr(want, f), f
    assert got.index == want.index
    assert set(got.nodes_by_id) == set(want.nodes_by_id)


@pytest.fixture()
def store():
    s = StateStore()
    for _ in range(24):
        s.upsert_node(mock.node())
    return s


class TestDeltaParity:
    def test_resource_change_delta_matches_fresh_build(self, store):
        cache = IncrementalClusterCache()
        cache.get(store.snapshot())
        node = store.snapshot().nodes()[5].copy()
        node.node_resources.cpu.cpu_shares = 12345
        node.node_resources.memory.memory_mb = 4096
        store.upsert_node(node)
        snap = store.snapshot()
        got = cache.get(snap)
        assert cache.delta_builds == 1
        assert_cluster_equal(got, ClusterTensors.build(snap.nodes()))

    def test_drain_and_status_delta(self, store):
        cache = IncrementalClusterCache()
        cache.get(store.snapshot())
        nodes = store.snapshot().nodes()
        store.update_node_drain(nodes[2].id, True)
        store.update_node_status(nodes[9].id, "down")
        snap = store.snapshot()
        got = cache.get(snap)
        assert cache.delta_builds == 1
        fresh = ClusterTensors.build(snap.nodes())
        assert_cluster_equal(got, fresh)
        # the drained/down rows really flipped
        assert not got.ready[2]
        assert not got.ready[9]

    def test_add_and_delete_delta(self, store):
        cache = IncrementalClusterCache()
        cache.get(store.snapshot())
        nodes = store.snapshot().nodes()
        store.delete_node(nodes[7].id)
        store.upsert_node(mock.node())
        store.upsert_node(mock.node())
        snap = store.snapshot()
        got = cache.get(snap)
        assert cache.delta_builds == 1
        assert_cluster_equal(got, ClusterTensors.build(snap.nodes()))

    def test_random_mutation_sequences(self, store):
        """Property-style: random interleavings of add / drain /
        resource-change / status / delete, parity after every batch."""
        rng = np.random.default_rng(11)
        cache = IncrementalClusterCache()
        cache.get(store.snapshot())
        for _round in range(6):
            for _ in range(int(rng.integers(1, 4))):
                nodes = store.snapshot().nodes()
                op = rng.integers(0, 5)
                pick = nodes[int(rng.integers(0, len(nodes)))]
                if op == 0:
                    store.upsert_node(mock.node())
                elif op == 1 and len(nodes) > 4:
                    store.delete_node(pick.id)
                elif op == 2:
                    n = pick.copy()
                    n.node_resources.cpu.cpu_shares = int(
                        rng.integers(1000, 9000))
                    store.upsert_node(n)
                elif op == 3:
                    store.update_node_drain(pick.id,
                                            bool(rng.integers(0, 2)))
                else:
                    store.update_node_status(
                        pick.id, "down" if rng.integers(0, 2) else "ready")
            snap = store.snapshot()
            got = cache.get(snap)
            assert_cluster_equal(got, ClusterTensors.build(snap.nodes()))
        assert cache.delta_builds >= 4

    def test_empty_base_falls_back_to_full_build(self):
        """A cluster snapshotted before any node registers caches an
        empty build; the first nodes arriving must take the full-build
        path (there are no rows to gather from)."""
        s = StateStore()
        cache = IncrementalClusterCache()
        empty = cache.get(s.snapshot())
        assert empty.n_real == 0
        for _ in range(4):
            s.upsert_node(mock.node())
        snap = s.snapshot()
        got = cache.get(snap)
        assert got.n_real == 4
        assert_cluster_equal(got, ClusterTensors.build(snap.nodes()))

    def test_pad_bucket_growth_falls_back_to_full_build(self):
        s = StateStore()
        for _ in range(60):
            s.upsert_node(mock.node())
        cache = IncrementalClusterCache()
        cache.get(s.snapshot())        # n_pad 64
        for _ in range(10):            # crosses into the 128 bucket
            s.upsert_node(mock.node())
        snap = s.snapshot()
        got = cache.get(snap)
        assert cache.full_builds == 2
        assert_cluster_equal(got, ClusterTensors.build(snap.nodes()))


class TestCacheBehavior:
    def test_same_version_is_identity_hit(self, store):
        cache = IncrementalClusterCache()
        snap = store.snapshot()
        c1 = cache.get(snap)
        assert cache.get(store.snapshot()) is c1
        assert cache.hits == 1

    def test_alloc_churn_does_not_invalidate(self, store):
        """Allocation transitions bump usage.version but not the node
        structure: the node planes must stay cached."""
        cache = IncrementalClusterCache()
        c1 = cache.get(store.snapshot())
        node = store.snapshot().nodes()[0]
        a = mock.alloc(node_id=node.id)
        store.upsert_allocs([a])
        assert cache.get(store.snapshot()) is c1

    def test_older_snapshot_stays_cached_alongside_newer(self, store):
        """A batch still scheduling against an older snapshot must keep
        getting ONE identical object per call (identity sharing is the
        wave launcher's upload layout), even after a newer structure
        version was cached."""
        cache = IncrementalClusterCache()
        old_snap = store.snapshot()
        c_old = cache.get(old_snap)
        store.upsert_node(mock.node())
        new_snap = store.snapshot()
        c_new = cache.get(new_snap)
        assert c_new is not c_old
        # the older version is still served by identity, not rebuilt
        builds_before = cache.full_builds + cache.delta_builds
        assert cache.get(old_snap) is c_old
        assert cache.get(old_snap) is c_old
        assert cache.full_builds + cache.delta_builds == builds_before
        # and the newer one too
        assert cache.get(new_snap) is c_new

    def test_trimmed_log_falls_back_to_full_build(self, store):
        from nomad_tpu.state import usage as usage_mod

        cache = IncrementalClusterCache()
        cache.get(store.snapshot())
        # more structural events than the log holds
        for _ in range(usage_mod.NODE_LOG_MAX // 2 + 4):
            store.upsert_node(mock.node())
            store.delete_node(store.snapshot().nodes()[-1].id)
        snap = store.snapshot()
        got = cache.get(snap)
        assert cache.full_builds == 2
        assert cache.delta_builds == 0
        assert_cluster_equal(got, ClusterTensors.build(snap.nodes()))


def _racked_store(n: int = 24, racks: int = 5) -> StateStore:
    s = StateStore()
    for i in range(n):
        node = mock.node()
        node.meta = {"rack": f"r{i % racks}"}
        s.upsert_node(node)
    return s


def _fresh_codes(snap, attribute):
    codes, values, built = ClusterTensors.build(
        snap.nodes()).spread_codes(attribute)
    assert built
    return codes, values


class TestSpreadCodes:
    """ISSUE 32: the node-static half of a spread stanza, once per
    cluster build and attribute."""

    RACK = "${meta.rack}"

    def test_codes_and_values(self):
        snap = _racked_store(7, racks=3).snapshot()
        c = ClusterTensors.build(snap.nodes())
        codes, values, built = c.spread_codes(self.RACK)
        assert built
        assert values == ("r0", "r1", "r2")       # first-seen row order
        assert codes.dtype == np.int32 and codes.shape == (c.n_pad,)
        npt.assert_array_equal(codes[:7], [0, 1, 2, 0, 1, 2, 0])
        assert (codes[7:] == -1).all()            # padded rows

    def test_missing_attribute_and_missing_node_are_minus_one(self):
        s = _racked_store(6, racks=2)
        bare = mock.node()
        bare.meta = {}
        s.upsert_node(bare)
        snap = s.snapshot()
        c = ClusterTensors.build(snap.nodes())
        gone = c.node_ids[1]
        del c.nodes_by_id[gone]
        codes, values, _ = c.spread_codes(self.RACK)
        assert codes[c.index[bare.id]] == -1
        assert codes[1] == -1
        assert values == ("r0", "r1")

    def test_four_threads_one_build(self, monkeypatch):
        import sys
        import threading
        import time

        from nomad_tpu.tensors.schema import spread_code_stats

        c = ClusterTensors.build(_racked_store().snapshot().nodes())
        walk = ClusterTensors._walk_spread_codes

        def slow_walk(self, attribute):
            time.sleep(0.05)            # hold the others at the lock
            return walk(self, attribute)

        monkeypatch.setattr(ClusterTensors, "_walk_spread_codes", slow_walk)
        spread_code_stats.reset()
        barrier = threading.Barrier(4)
        got = []

        def ask():
            barrier.wait()
            got.append(c.spread_codes(self.RACK))

        threads = [threading.Thread(target=ask) for _ in range(4)]
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        assert len(got) == 4
        assert all(g[0] is got[0][0] and g[1] is got[0][1] for g in got)
        assert sorted(g[2] for g in got) == [False, False, False, True]
        assert spread_code_stats.snapshot() == {"hits": 3, "builds": 1}

    def test_codes_are_frozen(self):
        c = ClusterTensors.build(_racked_store().snapshot().nodes())
        codes, _, _ = c.spread_codes(self.RACK)
        assert not codes.flags.writeable
        with pytest.raises(ValueError):
            codes[0] = 3

    def test_two_attributes_keep_two_entries(self):
        c = ClusterTensors.build(_racked_store().snapshot().nodes())
        rack, _, _ = c.spread_codes(self.RACK)
        dc, dc_values, built = c.spread_codes("${node.datacenter}")
        assert built and dc_values == ("dc1",)
        assert set(c._spread_codes) == {self.RACK, "${node.datacenter}"}
        assert c.spread_codes(self.RACK)[0] is rack
        assert c.spread_codes("${node.datacenter}")[0] is dc
        assert not c.spread_codes(self.RACK)[2]

    def test_meta_change_yields_a_new_build_with_fresh_codes(self):
        s = _racked_store()
        cache = IncrementalClusterCache()
        c1 = cache.get(s.snapshot())
        old_codes, old_values, _ = c1.spread_codes(self.RACK)
        kept = old_codes.copy()
        node = s.snapshot().nodes()[3].copy()
        node.meta = {"rack": "r-new"}
        s.upsert_node(node)
        snap = s.snapshot()
        c2 = cache.get(snap)
        assert cache.delta_builds == 1 and c2 is not c1
        assert c2._spread_codes == {}       # a delta build starts empty
        codes, values, built = c2.spread_codes(self.RACK)
        want_codes, want_values = _fresh_codes(snap, self.RACK)
        assert built
        npt.assert_array_equal(codes, want_codes)
        assert values == want_values and "r-new" in values
        # the older build, still serving its own snapshot, is untouched
        assert c1.spread_codes(self.RACK)[0] is old_codes
        npt.assert_array_equal(old_codes, kept)
        assert "r-new" not in old_values

    def test_rebuild_delta_directly(self):
        s = _racked_store()
        base = ClusterTensors.build(s.snapshot().nodes())
        base.spread_codes(self.RACK)
        nodes = s.snapshot().nodes()
        changed = nodes[0].copy()
        changed.meta = {}
        s.upsert_node(changed)
        s.delete_node(nodes[5].id)
        snap = s.snapshot()
        out = base.rebuild_delta(snap.nodes(), {changed.id, nodes[5].id})
        assert out is not None and out._spread_codes == {}
        codes, values, _ = out.spread_codes(self.RACK)
        want_codes, want_values = _fresh_codes(snap, self.RACK)
        npt.assert_array_equal(codes, want_codes)
        assert values == want_values
        assert codes[out.index[changed.id]] == -1

    @pytest.mark.parametrize("write", [
        "meta", "attributes", "node_class", "datacenter", "name",
        "status", "eligibility", "drain", "delete", "add",
    ])
    def test_every_node_write_moves_the_structure_version(self, write):
        """The cluster cache is keyed by the usage index's
        ``structure_version``; codes cached on a build are sound only
        if every write that can change what ``resolve_target`` reads
        moves it."""
        s = _racked_store()
        cache = IncrementalClusterCache()
        c1 = cache.get(s.snapshot())
        before = s.snapshot().usage.structure_version
        node = s.snapshot().nodes()[2].copy()
        attribute = {
            "meta": "${meta.rack}", "attributes": "${attr.arch}",
            "node_class": "${node.class}", "datacenter": "${node.datacenter}",
            "name": "${node.unique.name}",
        }.get(write, self.RACK)
        c1.spread_codes(attribute)
        if write == "meta":
            node.meta = {"rack": "elsewhere"}
        elif write == "attributes":
            node.attributes = dict(node.attributes, arch="arm64")
        elif write == "node_class":
            node.node_class = "another"
            node.computed_class = ""
        elif write in ("datacenter", "name"):
            setattr(node, write, "another")
        if write in ("meta", "attributes", "node_class", "datacenter",
                     "name"):
            s.upsert_node(node)
        elif write == "status":
            s.update_node_status(node.id, "down")
        elif write == "eligibility":
            s.update_node_eligibility(node.id, "ineligible")
        elif write == "drain":
            s.update_node_drain(node.id, True)
        elif write == "delete":
            s.delete_node(node.id)
        else:
            s.upsert_node(mock.node())
        snap = s.snapshot()
        assert snap.usage.structure_version > before
        c2 = cache.get(snap)
        assert c2 is not c1
        codes, values, built = c2.spread_codes(attribute)
        want_codes, want_values = _fresh_codes(snap, attribute)
        assert built
        npt.assert_array_equal(codes, want_codes)
        assert values == want_values

    def test_span_attribute_and_counters(self):
        from nomad_tpu import telemetry
        from nomad_tpu.scheduler.testing import Harness
        from nomad_tpu.structs.constraints import Spread
        from nomad_tpu.telemetry.trace import tracer
        from nomad_tpu.tensors.schema import spread_code_stats

        h = Harness()
        for i in range(12):
            node = mock.node()
            node.meta = {"rack": f"r{i % 4}"}
            h.state.upsert_node(node)
        telemetry.disable()
        telemetry.reset()
        tracer.enable()
        try:
            assert spread_code_stats.snapshot() == {"hits": 0, "builds": 0}
            hows = []
            for spread in (True, True, True, False):
                job = mock.job()
                job.task_groups[0].count = 3
                if spread:
                    job.task_groups[0].spreads = [
                        Spread(attribute=self.RACK, weight=100)]
                h.state.upsert_job(job)
                tracer.reset()
                h.process("service", mock.eval(job_id=job.id, type=job.type))
                spans = tracer.spans("sched.assembly")
                assert spans
                hows.append([s.attrs["spread_codes"] for s in spans])
            assert hows[0][0] == "built"
            assert set(hows[0][1:]) <= {"hit"}
            assert set(hows[1]) == set(hows[2]) == {"hit"}
            assert set(hows[3]) == {"none"}
            stats = spread_code_stats.snapshot()
            assert stats["builds"] == 1
            assert stats["hits"] == sum(len(x) for x in hows[:3]) - 1
        finally:
            telemetry.disable()
            telemetry.reset()
