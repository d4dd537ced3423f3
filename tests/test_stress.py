"""Contention-repetition tier (VERDICT r5 Weak #4).

``pytest -m stress`` runs each contention scenario N=20 times — the
load-flake class (r4's docker exec flake, r5's committed-broken test)
lives in thread interleavings a single run rarely hits. Every test
here is marked BOTH ``stress`` and ``slow``: tier-1 (`-m 'not slow'`)
never pays for repetition, and `-m stress` selects exactly this tier.
"""

import threading
import time

import numpy as np
import pytest

pytestmark = [pytest.mark.stress, pytest.mark.slow]

N_REPS = 20


@pytest.fixture(autouse=True)
def lock_witness():
    """Every stress cell runs under the runtime lock witness (ISSUE 9):
    brokers/coalescers/membership constructed inside the test get
    order-checked, hold-timed locks, and a cell that executes an
    acquisition-order inversion FAILS even if the interleaving never
    actually deadlocked. Hold-time distributions land in the
    ``lock_hold_*`` histograms (telemetry/histogram.py) as a side
    effect — pull them when a cell's p99 regresses.

    ``witness.enable()`` only instruments locks created AFTER it, and
    the module-level singletons (coalesce's inflight/stat locks,
    scaffold's cache lock) were created at import time as plain locks
    — so the fixture swaps witnessed locks into them for the tier and
    restores the originals after (no test may hold them across the
    fixture boundary; pytest guarantees that)."""
    import nomad_tpu.parallel.coalesce as co
    import nomad_tpu.scheduler.scaffold as sc
    from nomad_tpu.utils import witness

    witness.reset()
    witness.enable()
    swapped = [
        (co, "_INFLIGHT_LOCK", "coalesce._INFLIGHT_LOCK"),
        (sc, "_LOCK", "scaffold._LOCK"),
        (co.wave_stats, "_lock", "WaveStats._lock"),
        (co.wave_latency_ewma, "_lock", "LatencyEWMA._lock"),
        (co.wave_deadline_ewma, "_lock", "LatencyEWMA._lock"),
        (co.default_cluster_cache, "_lock", "ClusterCache._lock"),
    ]
    originals = []
    for obj, attr, name in swapped:
        originals.append((obj, attr, getattr(obj, attr)))
        setattr(obj, attr, witness.witness_lock(name))
    yield
    try:
        assert witness.violations() == [], (
            "lock-order inversion(s) under contention: "
            f"{witness.violations()}")
    finally:
        for obj, attr, orig in originals:
            setattr(obj, attr, orig)
        witness.disable()
        witness.reset()


class TestBrokerContention:
    def test_concurrent_enqueue_dequeue_ack(self):
        """Producers enqueue while consumers dequeue/ack: every eval is
        processed exactly once, none lost, none double-delivered."""
        from nomad_tpu import mock
        from nomad_tpu.server.eval_broker import EvalBroker

        for rep in range(N_REPS):
            broker = EvalBroker(nack_timeout=30.0)
            broker.set_enabled(True)
            n_per_producer, n_producers, n_consumers = 25, 4, 4
            total = n_per_producer * n_producers
            acked = []
            acked_lock = threading.Lock()

            def produce(pid):
                for i in range(n_per_producer):
                    ev = mock.eval()
                    ev.job_id = f"job-{pid}-{i}"   # distinct jobs: no dedup
                    broker.enqueue(ev)

            def consume():
                while True:
                    with acked_lock:
                        if len(acked) >= total:
                            return
                    batch = broker.dequeue_batch(
                        ["service"], 8, timeout=0.2)
                    for ev, token in batch:
                        broker.ack(ev.id, token)
                        with acked_lock:
                            acked.append(ev.id)

            threads = [threading.Thread(target=produce, args=(p,))
                       for p in range(n_producers)]
            threads += [threading.Thread(target=consume)
                        for _ in range(n_consumers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert len(acked) == total, f"rep {rep}: {len(acked)}/{total}"
            assert len(set(acked)) == total, f"rep {rep}: double delivery"
            broker.set_enabled(False)

    def test_nack_redelivery_under_contention(self):
        """Nacked evals (zero delay) must re-deliver exactly until the
        delivery limit, then land on the failed queue."""
        from nomad_tpu import mock
        from nomad_tpu.server.eval_broker import (
            FAILED_QUEUE, EvalBroker)

        for rep in range(N_REPS):
            broker = EvalBroker(nack_timeout=30.0, delivery_limit=3,
                                initial_nack_delay=0.0,
                                subsequent_nack_delay=0.0)
            broker.set_enabled(True)
            ev = mock.eval()
            broker.enqueue(ev)
            for _ in range(3):
                got, token = broker.dequeue(["service"], timeout=5.0)
                assert got is not None, f"rep {rep}: lost on redelivery"
                broker.nack(got.id, token)
            got, token = broker.dequeue([FAILED_QUEUE], timeout=5.0)
            assert got is not None, f"rep {rep}: not routed to failed"
            broker.set_enabled(False)


class TestCoalescerContention:
    def test_rendezvous_under_racing_done(self, monkeypatch):
        """Members race launch() against other members' done(): every
        launcher must get a result, regardless of interleaving (the
        wave fires from whichever thread completes the rendezvous)."""
        from nomad_tpu.parallel import coalesce

        def stub_launch_wave(kins, k_steps, features, mesh=None, **_record):
            time.sleep(0.001)
            return [object() for _ in kins]

        monkeypatch.setattr(coalesce, "launch_wave", stub_launch_wave)

        class KinStub:
            class _Arr:
                shape = (8,)
            cap_cpu = _Arr()

        for rep in range(N_REPS):
            n = 12
            launchers = list(np.random.RandomState(rep).rand(n) < 0.7)
            if not any(launchers):
                launchers[0] = True
            c = coalesce.LaunchCoalescer(n)
            results = [None] * n
            errors = []

            def member(i):
                try:
                    if launchers[i]:
                        results[i] = c.launch(KinStub(), 1, None)
                    else:
                        time.sleep(0.0005 * (i % 3))
                finally:
                    c.done()

            threads = [threading.Thread(target=member, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=20)
            assert not errors
            for i, is_launcher in enumerate(launchers):
                if is_launcher:
                    assert results[i] is not None, \
                        f"rep {rep}: member {i} never resumed"
            assert c.requests == sum(launchers)


class TestFleetCell:
    def test_fleet_cell_under_lock_witness(self):
        """ISSUE 11: the fleet cell (ring-cursor subscribers +
        heartbeat storm + held blocking queries over the new broker/
        watch paths) runs under the runtime lock witness — the autouse
        fixture fails the test on ANY executed acquisition-order
        inversion in the rebuilt EventBroker, the store's block_until,
        or the client-update fan-in batcher. One rep at reduced scale:
        the cell is itself a multi-thread contention storm; N=20 of it
        would dominate the tier for no added interleaving coverage."""
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench"))
        import trace_report

        cell = trace_report.run_fleet_burst(
            n_clients=2000, n_nodes=150, n_jobs=16, allocs_per_job=3,
            warmup_jobs=6, batch_size=8, deadline_s=120.0)
        assert cell["allocs_placed"] == cell["allocs_wanted"], cell
        assert cell["heartbeats"] > 0
        assert cell["watch_wakeups"] > 0
        assert cell["events_delivered"] > 0
        serving = cell["serving"]
        assert serving["stream"]["subscribers"] == 2000
        assert serving["stream"]["published_events"] > 0
        # the fan-in batcher coalesced the storm's alloc syncs
        assert serving["heartbeat"]["batches"] >= 1
        assert serving["heartbeat"]["callers"] >= \
            serving["heartbeat"]["batches"]
        # every committed eval landed in the e2e distribution
        assert cell["e2e_count"] == cell["committed_evals"]
        # delivery lag was measured (the serving plane's headline)
        assert cell["stream_deliver_count"] > 0


class TestReadPlaneCell:
    def test_readplane_cell_100k_three_servers_under_chaos(self):
        """ISSUE 20: the flagship read-plane cell — 100k streaming
        clients spread across a REAL 3-server cluster while a reader
        storm mixes stale/default/linearizable reads against every
        server, under BOTH standing chaos schedules (leader kill
        mid-storm; lease-partitioning the leader), all under the
        runtime lock witness (the autouse fixture fails the test on
        ANY executed acquisition-order inversion in the read plane's
        fence/forward paths). The standing gates: zero stale-read
        violations (no bounded-stale read ever served data older than
        its bound claimed), zero linearizable-from-lapsed-lease
        serves, follower share >= 0.66 (the read plane actually put
        the follower majority to work), and the stream gap-free or
        explicitly lost on every surviving server. One rep per
        schedule: each cell is itself a three-server fault storm."""
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench"))
        import trace_report

        for chaos in ("leader-kill-mid-wave", "lease-leader-partition"):
            cell = trace_report.run_fleet_burst(
                n_clients=100_000, n_servers=3, deadline_s=240.0,
                chaos=chaos)
            assert cell["clients"] == 100_000
            assert cell["servers"] == 3
            assert cell["converged_ok"], (chaos, cell["violations"])
            assert cell["stale_violations"] == 0, (chaos, cell)
            assert cell["linearizable_violations"] == 0, (chaos, cell)
            assert cell["lost_events"] == 0, (chaos, cell)
            assert cell["faults_fired"] >= 1, (chaos, cell)
            assert cell["read_follower_share"] >= 0.66, (chaos, cell)
            # the mode mix exercised every path: lease fast-path
            # linearizable reads, forwarded default fences, stale
            # serves off follower roots
            assert cell["read_lease_fast"] >= 1, (chaos, cell)
            assert cell["read_forwards"] >= 1, (chaos, cell)
            assert cell["read_served"]["follower"] >= 1, (chaos, cell)
            if chaos == "lease-leader-partition":
                # the probe actually cornered the deposed leader: the
                # partition landed, its lease lapsed, and every read it
                # answered after the new side committed either demoted
                # to the barrier or was refused — never a lease-valid
                # serve of stale data
                probe = cell["lease_probe"]
                assert probe["partitioned"], (chaos, cell)
                assert probe["demoted"] >= 1, (chaos, cell)
                assert probe["fast_stale"] == 0, (chaos, cell)


class TestMeshCell:
    def test_mesh_cell_100k_nodes_under_lock_witness(self):
        """ISSUE 14: the full-shape mesh cell — 100k heterogeneous
        nodes / 1M resident allocs, waves sharded over the 8-device
        host mesh — under the runtime lock witness (the autouse
        fixture fails the test on ANY executed acquisition-order
        inversion in the registry/advance locking the sharded path
        exercises from eval threads). The standing gates: every wave
        dispatched sharded (zero fallbacks), outputs bit-identical to
        the single-device reference, steady window compile-free,
        dirty-row advancement sharded with no full-plane d2h gathers.
        One rep: coverage comes from the scale, not repetition."""
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench"))
        import trace_report

        cell = trace_report.run_mesh_burst(deadline_s=20.0)
        assert cell["devices"] == 8
        assert cell["nodes"] == 100_000
        assert cell["allocs_resident"] == 1_000_000
        assert cell["waves"] >= 4
        assert cell["parity_ok"], cell
        assert cell["sharded_fallbacks"] == 0, cell
        assert cell["sharded_launches"] == cell["waves"]
        assert cell["jit_cache_misses"] == 0, cell
        assert cell["allocs_placed"] > 0
        # dirty-row advancement stayed sharded: every between-wave
        # ensure was a delta scatter, never a full usage re-upload,
        # and the uploaded bytes are a sliver of full re-uploads
        assert cell["delta_advances"] >= cell["waves"]
        assert cell["usage_full_uploads"] == 0, cell
        assert cell["dirty_row_upload_ratio"] <= 0.05, cell
        # no per-wave full-plane gathers: d2h stays the small
        # replicated per-placement rows
        assert cell["no_full_gather_ok"], cell
        # ISSUE 19: with fusion on by default every steady mesh wave
        # runs the fused sharded program at ONE dispatch per wave
        assert cell["fused_launches"] == cell["waves"], cell
        assert cell["fused_fallbacks"] == 0, cell
        assert cell["dispatches_per_wave"] == 1.0, cell


class TestWorkerCell:
    def test_worker_cell_under_lock_witness(self):
        """ISSUE 17: the multi-process worker cell's A/B burst under
        the runtime lock witness — the owner-side supervisor (dispatch
        loop, per-worker handles, lease ledger, state-sync lock) plus
        the generation-lease registry run with order-checked locks and
        the test fails on ANY executed acquisition-order inversion.
        Reduced scale, one rep: the cell already runs two full server
        topologies (in-process threads, then worker processes); the
        witness coverage comes from the owner side — the child
        processes have their own interpreters the witness cannot see.
        Speedup is NOT asserted (this tier runs on whatever cores CI
        gives it); parity, drained leases, and fault-free lease
        accounting are."""
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench"))
        import trace_report

        cell = trace_report.run_worker_burst(
            n_workers=2, n_nodes=60, n_jobs=16, allocs_per_job=3,
            warmup_jobs=4, batch_size=8, deadline_s=120.0)
        assert cell["parity_ok"], cell
        assert cell["baseline"]["allocs_placed"] == \
            cell["baseline"]["allocs_wanted"], cell
        assert cell["multi"]["allocs_placed"] == \
            cell["multi"]["allocs_wanted"], cell
        # fault-free burst: no lease ever timed out or was reissued
        assert cell["lease_reissues"] == 0, cell
        assert cell["respawns"] == 0, cell
        # the supervisor pinged its workers and measured round-trips
        assert cell["ipc_rtts"] > 0
        # steady-window gates (owner-side)
        assert cell["jit_cache_misses"] == 0, cell
        assert cell["plan_group_fallbacks"] == 0, cell
        # both topologies torn down: no generation lease survives
        assert cell["leases_leaked"] == 0, cell


class TestChaosCell:
    def test_chaos_suite_under_lock_witness(self):
        """ISSUE 12: every standing chaos schedule (leader-kill-mid-
        wave, plan-commit raft failure, crash-and-drop) against a live
        3-node raft cluster, pinned seed, under the runtime lock
        witness (the autouse fixture fails the test on ANY executed
        acquisition-order inversion in the failover/unwind paths the
        faults force). All convergence invariants must hold — every
        eval terminal, exact placement, usage planes bit-identical to
        a from-scratch rebuild on every replica, dropped nodes down
        and drained, stream gap-free or explicitly lost. One rep: the
        cell is itself a three-server fault storm; its coverage comes
        from the schedules, not repetition."""
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench"))
        import trace_report

        import tempfile

        from nomad_tpu.telemetry.timeline import validate_timeline

        with tempfile.TemporaryDirectory() as td:
            tl_path = os.path.join(td, "CHAOS_TIMELINE.json")
            suite = trace_report.run_chaos_suite(deadline_s=90.0,
                                                 settle_s=60.0,
                                                 timeline_path=tl_path)
            assert os.path.exists(tl_path)
        assert suite["converged_ok"], suite["violations"]
        assert suite["faults_fired"] >= 3
        for name, r in suite["schedules"].items():
            assert r["converged_ok"], (name, r["violations"])
            assert r["allocs_placed"] == r["allocs_wanted"], (name, r)
            # ISSUE 15: every schedule's timeline is a valid artifact
            assert validate_timeline(r["timeline"]) == [], \
                (name, validate_timeline(r["timeline"]))
        # the schedules did what they say on the tin
        assert suite["schedules"]["leader-kill-mid-wave"][
            "faults"]["raft.leader.stepdown"]["fires"] == 1
        assert suite["schedules"]["crash-and-drop"]["nodes_down"] == 3
        assert suite["schedules"]["plan-commit-raft-failure"][
            "faults"]["plan.commit.raft"]["fires"] >= 1
        # ISSUE 17: the worker-kill schedule SIGKILLed real worker
        # processes mid-lease and lease recovery ran (re-enqueue +
        # respawn) — converged_ok above already proved every eval
        # terminal and placement exact THROUGH the process deaths
        wk = suite["schedules"]["worker-kill-mid-lease"]
        assert wk["faults"]["workerproc.kill"]["fires"] >= 1, wk
        assert wk["worker_lease_reissues"] >= 1, wk
        assert wk["worker_respawns"] >= 1, wk
        # ISSUE 15: the leader-kill schedule produced a failover and
        # >= 0.90 of the suite's failover wall is phase-attributed
        tl = suite["timeline"]
        assert tl["failovers"] >= 1, suite["schedules"][
            "leader-kill-mid-wave"]["timeline"]["events"]
        assert tl["attributed_share"] >= 0.9, tl
        # ISSUE 18: the lease-partition schedule's probe actually ran
        # (the lease lapsed — barrier reads observed) and the deposed
        # leader NEVER served a lease-valid read after the new side
        # committed past it (the zero-stale-reads safety gate)
        ls = suite["schedules"]["lease-leader-partition"]
        assert ls["lease_fast_stale_reads"] == 0, ls
        assert ls["lease_barrier_reads"] >= 1, ls
        assert ls["lease_fast_reads"] >= 1, ls


class TestRaftCell:
    def test_raft_cell_under_lock_witness(self):
        """ISSUE 18: the pipelined-vs-synchronous A/B under the
        runtime lock witness — the per-peer wire turnstile
        (raft_pipe_wire) is a new witnessed leaf under raft_node, so
        any executed acquisition-order inversion in the window
        fill/ack/drain paths fails the cell. The bench gates are
        asserted too: the speedup comes from overlapping INJECTED 5ms
        send latency (not from cores), so it holds on whatever box CI
        gives this tier — and a speedup with diverged logs or a
        drain storm is a regression, not a win."""
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench"))
        import trace_report

        cell = trace_report.run_raft_burst()
        assert cell["logs_identical"], cell
        assert not cell["sync"]["errors"], cell["sync"]["errors"]
        assert not cell["pipelined"]["errors"], \
            cell["pipelined"]["errors"]
        # the sync arm must never touch the window; the pipelined arm
        # must actually use it
        assert cell["sync"]["pipeline_batches"] == 0, cell["sync"]
        assert cell["pipelined"]["pipeline_batches"] > 0, \
            cell["pipelined"]
        assert cell["speedup_ok"], (cell["speedup"],
                                    cell["lag_improvement"])


class TestRestartCell:
    def test_restart_chaos_and_torn_fuzz_under_lock_witness(self):
        """ISSUE 13: the kill→restart recovery cell (torn-write kill +
        clean leader kill against a data_dir-backed 3-node cluster)
        under the runtime lock witness — the new WAL/stable-store
        locks are witness-created, so any executed acquisition-order
        inversion in the durability paths fails the cell. All recovery
        invariants must hold: no acked committed write lost, usage
        planes bit-identical on every restarted replica, no double
        vote in any term, stream resume explicit. Plus the full
        ≥200-seed torn-tail fuzz: recovery either truncates cleanly or
        fails loudly — never silently diverges."""
        import os
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))), "bench"))
        import trace_report

        from nomad_tpu.telemetry.timeline import validate_timeline

        cell = trace_report.run_restart_chaos(deadline_s=90.0,
                                              settle_s=45.0)
        assert cell["converged_ok"], cell["violations"]
        assert cell["restarts"] == 2, cell
        assert cell["torn_truncations"] >= 1, cell
        assert cell["replayed_entries"] > 0, cell
        assert cell["allocs_placed"] == cell["allocs_wanted"], cell
        assert cell["stream_missed_alloc_events"] == 0 or \
            cell["stream_lost_markers"] > 0, cell
        # ISSUE 15: the restart legs produced a valid, attributed
        # failover timeline (killed leader -> elect -> replay ->
        # converge), recovery events included
        tl = cell["timeline"]
        assert validate_timeline(tl) == [], validate_timeline(tl)
        assert len(tl["failovers"]) >= 1, tl["events"]
        assert tl["attribution"]["share"] >= 0.9, tl["failovers"]
        assert any(e["kind"] == "recovery" for e in tl["events"]), \
            tl["events"]

        fuzz = trace_report.run_torn_tail_fuzz(seeds=200)
        assert fuzz["silent_divergences"] == 0, fuzz
        assert fuzz["clean_prefix"] > 0 and fuzz["loud_corruption"] > 0


class TestMembershipContention:
    def test_reconcile_queue_preserves_event_order(self):
        """The satellite fix itself: MEMBER_FAILED/MEMBER_ALIVE flap
        pairs must reach the reconcile handler in arrival order (the
        old thread-per-event dispatch let the OS scheduler reorder
        them and flip raft membership the wrong way)."""
        from nomad_tpu.api.agent import SerialEventWorker

        for rep in range(N_REPS):
            seen = []
            worker = SerialEventWorker(
                lambda kind, m: seen.append((kind, m["Name"])))
            expect = []
            for i in range(50):
                kind = "member-failed" if i % 2 == 0 else "member-alive"
                worker.submit(kind, {"Name": f"srv-{i % 3}"})
                expect.append((kind, f"srv-{i % 3}"))
            deadline = time.time() + 10
            while len(seen) < len(expect) and time.time() < deadline:
                time.sleep(0.005)
            worker.shutdown()
            assert seen == expect, f"rep {rep}: events reordered"

    def test_concurrent_merge_respects_incarnation_precedence(self):
        """Gossip merges racing from multiple threads (the rx path vs
        the prober) must converge on the highest-incarnation status."""
        from nomad_tpu.server.membership import ALIVE, FAILED, Membership

        for rep in range(N_REPS):
            m = Membership(name="self", probe_interval=60.0)
            try:
                rows_a = [["peer", "127.0.0.1", 9999, inc,
                           ALIVE if inc % 2 else FAILED, {}]
                          for inc in range(1, 41)]
                rows_b = list(reversed(rows_a))

                def merge(rows):
                    for row in rows:
                        with m._lock:
                            m._merge_locked(list(row))

                ta = threading.Thread(target=merge, args=(rows_a,))
                tb = threading.Thread(target=merge, args=(rows_b,))
                ta.start(); tb.start()
                ta.join(10); tb.join(10)
                peer = m._members["peer"]
                assert peer.inc == 40, f"rep {rep}: inc {peer.inc}"
                assert peer.status == FAILED, f"rep {rep}: {peer.status}"
            finally:
                m.shutdown(leave=False)
