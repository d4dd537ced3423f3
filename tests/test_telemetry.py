"""The telemetry subsystem (ISSUE 1): span tracing, kernel profiling,
exposition, and the live-path trace decomposition.

Covers the acceptance surface directly:
- span nesting + cross-thread propagation (exclusive-time accounting)
- disabled-mode overhead (the no-op fast path)
- jit cache-miss counter correctness under re-used bucket shapes
- /v1/metrics Prometheus text + /v1/operator/traces against the real
  HTTP API, including the ACL gate
- the e2e traced burst emitting a TRACE_DECOMP stage decomposition
  that attributes >= 90% of per-eval wall time to named spans
"""

import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import pytest

from nomad_tpu import telemetry
from nomad_tpu.telemetry.exporter import prometheus_text, traces_json
from nomad_tpu.telemetry.kernel_profile import profiler
from nomad_tpu.telemetry.trace import Tracer, tracer

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "bench"))


@pytest.fixture()
def clean_telemetry():
    """Enable + reset around a test; restore disabled state after."""
    telemetry.enable()
    telemetry.reset()
    yield
    telemetry.disable()
    telemetry.reset()


class TestSpans:
    def test_nesting_and_exclusive_time(self):
        t = Tracer()
        t.enable()
        with t.span("outer", trace_id="t1"):
            time.sleep(0.01)
            with t.span("inner"):
                time.sleep(0.02)
        spans = {s.name: s for s in t.spans()}
        assert spans["inner"].parent_id == spans["outer"].span_id
        assert spans["inner"].trace_id == "t1"
        assert spans["outer"].dur_s >= 0.028
        # outer's exclusive excludes inner's whole duration
        assert spans["outer"].exclusive_s <= spans["outer"].dur_s - 0.015
        agg = t.stage_totals()
        assert agg["outer"]["count"] == 1
        assert agg["outer"]["exclusive_s"] < agg["outer"]["total_s"]

    def test_cross_thread_propagation(self):
        t = Tracer()
        t.enable()
        got = {}

        with t.span("root", trace_id="trace-x") as root:
            ctx = t.context()

            def worker():
                with t.attach(ctx):
                    with t.span("child"):
                        pass
                # attach scope ends: a new root span is unparented
                with t.span("orphan"):
                    pass
                got["done"] = True

            th = threading.Thread(target=worker)
            th.start()
            th.join()

        assert got["done"]
        child = t.spans(name="child")[0]
        assert child.trace_id == "trace-x"
        assert child.parent_id == root.span_id
        orphan = t.spans(name="orphan")[0]
        assert orphan.parent_id == 0

    def test_exception_unwinds_stack(self):
        t = Tracer()
        t.enable()
        with pytest.raises(RuntimeError):
            with t.span("a"):
                with t.span("b"):
                    raise RuntimeError("boom")
        # stack fully unwound: a new span is a root again
        with t.span("c"):
            pass
        assert t.spans(name="c")[0].parent_id == 0

    def test_ring_is_bounded_but_aggregates_are_not(self):
        t = Tracer(capacity=8)
        t.enable()
        for _ in range(50):
            with t.span("x"):
                pass
        assert len(t.spans()) == 8
        assert t.stage_totals()["x"]["count"] == 50

    def test_disabled_mode_is_cheap(self):
        """The disabled path must be a near-no-op: no allocation, no
        clock. Bound it RELATIVE to the enabled path (absolute
        thresholds flake on loaded CI)."""
        t = Tracer()
        n = 20_000

        t.enable()
        t0 = time.perf_counter()
        for _ in range(n):
            with t.span("s"):
                pass
        enabled_s = time.perf_counter() - t0

        t.disable()
        t0 = time.perf_counter()
        for _ in range(n):
            with t.span("s"):
                pass
        disabled_s = time.perf_counter() - t0

        assert disabled_s < enabled_s / 3
        # and nothing was recorded
        assert t.stage_totals()["s"]["count"] == n

    def test_record_after_the_fact_parents_under_open_span(self):
        t = Tracer()
        t.enable()
        with t.span("parent") as p:
            t.record("leaf", 0.005)
        leaf = t.spans(name="leaf")[0]
        assert leaf.parent_id == p.span_id
        parent = t.spans(name="parent")[0]
        assert parent.child_s >= 0.005


class TestKernelProfiler:
    def test_cache_miss_counting_under_reused_bucket_shapes(
            self, clean_telemetry):
        """Two launches with the SAME bucket key: one compile, one
        cache hit. A third with a new key: another miss. Uses a real
        jit function so the cache-growth cross-check exercises."""
        import jax
        import jax.numpy as jnp

        fn = jax.jit(lambda x, k: x * k, static_argnums=(1,))
        key_a = ("bucket", 64)
        out1 = profiler.call("toy", fn, (jnp.ones(64),), (2,), key_a,
                             jit_fn=fn)
        out2 = profiler.call("toy", fn, (jnp.ones(64),), (2,), key_a,
                             jit_fn=fn)
        assert float(out1[0]) == 2.0 and float(out2[0]) == 2.0
        assert profiler.misses_for("toy") == 1

        key_b = ("bucket", 128)
        profiler.call("toy", fn, (jnp.ones(128),), (2,), key_b, jit_fn=fn)
        assert profiler.misses_for("toy") == 2

        s = profiler.summary()
        assert s["Launches"] == 3
        assert s["JitCacheMisses"] == 2
        # cross-check agrees with the seen-set on a well-bucketed kernel
        assert s["JitCacheGrowth"] == 2
        assert s["StageSeconds"]["execute"] >= 0.0
        assert s["StageSeconds"]["h2d"] > 0.0

    def test_live_wave_records_kernel_stages(self, clean_telemetry):
        """A real coalesced wave populates the kernel spans and the
        per-key accounting (two same-shape waves -> one compile)."""
        from nomad_tpu import mock
        from nomad_tpu.server.server import Server, ServerConfig

        server = Server(ServerConfig(num_workers=1, worker_batch_size=4,
                                     heartbeat_ttl=3600.0))
        server.start()
        try:
            for _ in range(20):
                server.node_register(mock.node())
            jobs = []
            for _ in range(8):
                job = mock.simple_job()
                job.task_groups[0].count = 2
                jobs.append(job)
                server.job_register(job)
            deadline = time.time() + 60
            while time.time() < deadline:
                snap = server.state.snapshot()
                if sum(len(snap.allocs_by_job(j.namespace, j.id))
                       for j in jobs) >= 16:
                    break
                time.sleep(0.05)
            stages = tracer.stage_totals()
            for name in ("broker.dequeue", "worker.snapshot",
                         "eval.schedule", "wave.assemble", "kernel.h2d",
                         "kernel.execute", "kernel.d2h", "plan.evaluate",
                         "plan.group_commit", "plan.commit", "fsm.apply"):
                assert name in stages, f"missing span {name}"
            prof = profiler.summary()
            assert prof["Launches"] >= 1
            # repeated same-bucket waves must not recompile
            assert prof["JitCacheMisses"] <= len(prof["PerKey"])
        finally:
            server.shutdown()


class TestExposition:
    def test_prometheus_text_includes_telemetry_series(
            self, clean_telemetry):
        with tracer.span("unit.test.span"):
            pass
        text = prometheus_text()
        assert "# TYPE nomad_tpu_trace_span_seconds_total counter" in text
        assert 'nomad_tpu_trace_span_seconds_total{span="unit.test.span"}' \
            in text
        assert "nomad_tpu_telemetry_enabled 1" in text
        # transfer byte counters + device-residency series (ISSUE 3)
        assert 'nomad_tpu_kernel_transfer_bytes_total{direction="h2d"}' \
            in text
        assert 'nomad_tpu_kernel_transfer_bytes_total{direction="d2h"}' \
            in text
        assert "nomad_tpu_device_state_dirty_row_upload_ratio" in text
        # plan group-commit series (ISSUE 6)
        assert 'nomad_tpu_plan_group_plans_total{kind="vector"}' in text
        assert 'nomad_tpu_plan_group_plans_total{kind="fallback"}' in text
        assert "nomad_tpu_plan_group_commits_total" in text
        assert "nomad_tpu_plan_group_rejects_total" in text
        assert "nomad_tpu_plan_group_bytes_total" in text

    def test_prometheus_latency_histograms(self, clean_telemetry):
        """ISSUE 8: streaming latency histograms export as the real
        Prometheus histogram type — cumulative _bucket/_sum/_count."""
        from nomad_tpu.telemetry.histogram import histograms

        for v in (0.002, 0.004, 0.050):
            histograms.get("e2e").record(v)
        histograms.get("wave_park").record(0.001)
        text = prometheus_text()
        assert "# TYPE nomad_tpu_latency_seconds histogram" in text
        assert 'nomad_tpu_latency_seconds_bucket{op="e2e",le="' in text
        assert 'nomad_tpu_latency_seconds_bucket{op="e2e",le="+Inf"} 3' \
            in text
        assert 'nomad_tpu_latency_seconds_count{op="e2e"} 3' in text
        assert 'nomad_tpu_latency_seconds_sum{op="e2e"} 0.056' in text
        assert 'nomad_tpu_latency_seconds_count{op="wave_park"} 1' \
            in text
        # flight-recorder health series ride along
        assert "nomad_tpu_slow_evals_captured_total" in text
        assert "nomad_tpu_slow_eval_threshold_seconds" in text
        # cumulative bucket counts are non-decreasing per op
        cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
                if line.startswith(
                    'nomad_tpu_latency_seconds_bucket{op="e2e"')]
        assert cums == sorted(cums)

    def test_traces_json_shape(self, clean_telemetry):
        with tracer.span("a", trace_id="t"):
            pass
        body = traces_json()
        assert body["Enabled"] is True
        assert body["Stages"]["a"]["Count"] == 1
        assert body["Spans"][-1]["Name"] == "a"
        assert "Kernel" in body


def _get(addr: str, path: str, token: str = ""):
    req = urllib.request.Request(addr + path)
    if token:
        req.add_header("X-Nomad-Token", token)
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, resp.headers, resp.read()


class TestHTTPEndpoints:
    @pytest.fixture()
    def agent(self):
        from nomad_tpu.api.agent import Agent, AgentConfig
        from nomad_tpu.server.core_sched import ALL_CORE_JOBS

        a = Agent(AgentConfig(serf_enabled=False))
        a.start()
        # a new leader enqueues its core GC evals at once; let them
        # finish, so that none records its e2e sample after
        # clean_telemetry's reset (the histogram counts are exact)
        deadline = time.time() + 30
        while sum(w.processed for w in a.server.workers) \
                < len(ALL_CORE_JOBS) and time.time() < deadline:
            time.sleep(0.01)
        try:
            yield a
        finally:
            a.shutdown()

    def test_metrics_prometheus_is_raw_text(self, agent, clean_telemetry):
        with tracer.span("http.test"):
            pass
        status, headers, body = _get(
            agent.http.addr, "/v1/metrics?format=prometheus")
        assert status == 200
        assert headers["Content-Type"].startswith("text/plain")
        text = body.decode()
        # raw exposition, not a JSON-quoted string
        assert text.startswith("#") or text.startswith("nomad")
        assert "nomad_tpu_telemetry_enabled" in text
        assert 'span="http.test"' in text

    def test_metrics_default_is_json_summary(self, agent):
        status, headers, body = _get(agent.http.addr, "/v1/metrics")
        assert status == 200
        data = json.loads(body)
        assert "Counters" in data and "Samples" in data

    def test_operator_traces_roundtrip(self, agent, clean_telemetry):
        with tracer.span("op.span", trace_id="t9"):
            pass
        status, _, body = _get(agent.http.addr, "/v1/operator/traces")
        assert status == 200
        data = json.loads(body)
        assert data["Enabled"] is True
        assert any(s["Name"] == "op.span" for s in data["Spans"])

    def test_operator_traces_trace_id_filter(self, agent,
                                             clean_telemetry):
        """?trace_id= narrows the span dump to one eval's tree
        (Tracer.spans already filters; this is the HTTP plumbing)."""
        with tracer.span("filter.a", trace_id="trace-a"):
            pass
        with tracer.span("filter.b", trace_id="trace-b"):
            pass
        status, _, body = _get(
            agent.http.addr, "/v1/operator/traces?trace_id=trace-a")
        assert status == 200
        data = json.loads(body)
        assert data["TraceID"] == "trace-a"
        assert data["Spans"]
        assert all(s["TraceID"] == "trace-a" for s in data["Spans"])
        assert not any(s["Name"] == "filter.b" for s in data["Spans"])

    def test_operator_slow_evals_roundtrip(self, agent,
                                           clean_telemetry):
        """GET /v1/operator/slow-evals serves the flight recorder's
        captured trees + threshold + histogram summaries."""
        from nomad_tpu.telemetry.histogram import histograms
        from nomad_tpu.telemetry.trace import flight_recorder

        e2e = histograms.get("e2e")
        for i in range(flight_recorder.MIN_SAMPLES):
            e2e.record(0.01)
            flight_recorder.observe(f"fast-{i}", 0.01)
        with tracer.span("eval.schedule", trace_id="slow-1"):
            pass
        e2e.record(5.0)
        assert flight_recorder.observe("slow-1", 5.0)
        status, _, body = _get(agent.http.addr,
                               "/v1/operator/slow-evals")
        assert status == 200
        data = json.loads(body)
        assert data["Captured"] >= 1
        assert data["ThresholdMs"] > 0
        assert data["Trees"]
        tree = data["Trees"][-1]
        assert tree["TraceID"] == "slow-1"
        assert any(s["Name"] == "eval.schedule"
                   for s in tree["Spans"])
        assert data["Histogram"]["e2e"]["count"] == \
            flight_recorder.MIN_SAMPLES + 1


class TestServingPlane:
    """ISSUE 11: the serving-plane observability surface — the
    stream-health endpoint, the nomad_tpu_stream_*/watch/heartbeat/
    wave-cohort Prometheus series, and the fleet_* bench-key contract."""

    @pytest.fixture()
    def agent(self):
        from nomad_tpu.api.agent import Agent, AgentConfig

        a = Agent(AgentConfig(serf_enabled=False))
        a.start()
        try:
            yield a
        finally:
            a.shutdown()

    def test_stream_health_endpoint(self, agent, clean_telemetry):
        from nomad_tpu import mock

        server = agent.server
        sub = server.event_broker.subscribe({"*": ["*"]})
        server.job_register(mock.job())
        evs = sub.next_events(timeout=5.0)
        assert evs
        status, _, body = _get(agent.http.addr,
                               "/v1/operator/stream-health")
        assert status == 200
        data = json.loads(body)
        assert data["Stream"]["published_events"] >= 1
        assert data["Stream"]["delivered_events"] >= 1
        assert data["Stream"]["subscribers"] >= 1
        assert "held_watchers" in data["Watch"]
        assert "wakeups" in data["Watch"]
        assert "heartbeats" in data["Heartbeat"]
        assert "batches" in data["Heartbeat"]
        # the delivery-lag histogram recorded the hand-off above
        assert data["DeliverLatency"].get("count", 0) >= 1
        sub.close()

    def test_serving_prometheus_series(self, agent, clean_telemetry):
        """The serving-plane series ride the standard scrape: stream
        ring gauges (per-server, passed by the HTTP layer), watch
        wakeups, heartbeat fan-in, and the ISSUE 11 satellite's
        wave-cohort gauges."""
        from nomad_tpu import mock

        server = agent.server
        sub = server.event_broker.subscribe({"*": ["*"]})
        node = mock.node()
        server.node_register(node)
        server.node_heartbeat(node.id, "ready")
        # a held-then-woken blocking query feeds the watch counters
        idx = server.state.table_index(["jobs"])
        waiter = threading.Thread(
            target=lambda: server.state.block_until(["jobs"], idx, 5.0),
            daemon=True)
        waiter.start()
        time.sleep(0.1)
        server.job_register(mock.job())
        waiter.join(timeout=5.0)
        sub.next_events(timeout=5.0)
        status, _, body = _get(
            agent.http.addr, "/v1/metrics?format=prometheus")
        assert status == 200
        text = body.decode()
        for series in (
            "nomad_tpu_stream_subscribers",
            'nomad_tpu_stream_events_total{kind="published"}',
            'nomad_tpu_stream_events_total{kind="delivered"}',
            'nomad_tpu_stream_events_total{kind="lost"}',
            "nomad_tpu_stream_max_lag_events",
            "nomad_tpu_stream_retained_events",
            "nomad_tpu_stream_delivered_bytes_total",
            "nomad_tpu_watch_held_watchers",
            'nomad_tpu_watch_wakeups_total{kind="real"}',
            'nomad_tpu_watch_wakeups_total{kind="spurious"}',
            "nomad_tpu_heartbeats_total",
            'nomad_tpu_client_update_fanin_total{kind="batches"}',
            "nomad_tpu_wave_cohort_waves_total",
            "nomad_tpu_wave_cohort_plans_total",
            'nomad_tpu_wave_cohort_outcomes_total{kind="drained"}',
            'nomad_tpu_wave_cohort_outcomes_total{kind="hard_cap"}',
            "nomad_tpu_wave_cohort_drain_ewma_seconds",
            'nomad_tpu_latency_seconds_bucket{op="stream_deliver"',
        ):
            assert series in text, series
        # the watch thread above must have produced a real wakeup
        import re as _re

        m = _re.search(
            r'nomad_tpu_watch_wakeups_total\{kind="real"\} (\d+)', text)
        assert m and int(m.group(1)) >= 1, m
        sub.close()

    def test_fleet_bench_keys_emitted(self):
        """The fleet cell's trend lines are contract: bench.py must
        emit the fleet_* keys the serving-plane work gates on (the
        graftcheck R5 rule holds them against TELEMETRY.md both
        directions; this pins the REQUIRED core set)."""
        import ast

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        with open(os.path.join(repo, "bench.py")) as f:
            tree = ast.parse(f.read())
        emitted = {
            kw.arg
            for node in ast.walk(tree) if isinstance(node, ast.Call)
            for kw in node.keywords
            if kw.arg and kw.arg.startswith("fleet_")
        }
        assert {
            "fleet_clients",
            "fleet_heartbeats_per_sec",
            "fleet_watch_wakeups_per_sec",
            "fleet_stream_deliver_p99_ms",
            "fleet_e2e_p99_ms",
            "fleet_e2e_p99_held",
        } <= emitted, emitted

    def test_client_update_fan_in_coalesces_concurrent_callers(self):
        """Heartbeat fan-in batching: concurrent Node.UpdateAlloc
        callers must merge into fewer ALLOC_CLIENT_UPDATE raft entries
        (one per drain) with every caller seeing a committed index."""
        from nomad_tpu import mock
        from nomad_tpu.server.server import (
            Server,
            ServerConfig,
            client_update_stats,
        )

        server = Server(ServerConfig(num_workers=0,
                                     heartbeat_ttl=3600.0,
                                     client_update_fill_window_ms=5.0))
        server.start()
        try:
            node = mock.node()
            server.node_register(node)
            allocs = []
            for _ in range(16):
                a = mock.alloc(node_id=node.id)
                server.state.upsert_allocs([a])
                allocs.append(a)
            client_update_stats.reset_stats()
            applies0 = server.state.latest_index()
            results = [None] * len(allocs)

            def report(k):
                results[k] = server.update_allocs_from_client(
                    [allocs[k]])

            threads = [threading.Thread(target=report, args=(k,))
                       for k in range(len(allocs))]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            snap = client_update_stats.snapshot()
            assert snap["callers"] == len(allocs)
            assert snap["allocs"] == len(allocs)
            # coalescing happened: strictly fewer raft entries than
            # callers (16 concurrent updates against a >=5ms window
            # cannot all land in distinct batches)
            assert snap["batches"] < len(allocs), snap
            assert all(isinstance(r, int) and r > applies0
                       for r in results)
            # every alloc's update actually committed
            state_snap = server.state.snapshot()
            assert all(state_snap.alloc_by_id(a.id) is not None
                       for a in allocs)
        finally:
            server.shutdown()


class TestTracesACL:
    """/v1/operator/traces is gated like the event stream: a token
    without operator:read is rejected outright."""

    @pytest.fixture()
    def acl_agent(self):
        from nomad_tpu.acl.policy import ACLPolicy, ACLToken
        from nomad_tpu.api.agent import Agent, AgentConfig
        from nomad_tpu.server import fsm as fsm_msgs

        cfg = AgentConfig(acl_enabled=True, serf_enabled=False)
        agent = Agent(cfg)
        agent.start()
        server = agent.server
        # bootstrap a management token + a no-capability token
        mgmt = ACLToken.create(name="mgmt", type="management")
        server.raft_apply(fsm_msgs.ACL_TOKEN_UPSERT, {"tokens": [mgmt]})
        policy = ACLPolicy(name="job-read",
                           rules='namespace "default" { policy = "read" }')
        server.raft_apply(fsm_msgs.ACL_POLICY_UPSERT,
                          {"policies": [policy]})
        weak = ACLToken.create(name="weak", type="client",
                               policies=["job-read"])
        server.raft_apply(fsm_msgs.ACL_TOKEN_UPSERT, {"tokens": [weak]})
        try:
            yield agent, mgmt.secret_id, weak.secret_id
        finally:
            agent.shutdown()

    def test_anonymous_and_weak_tokens_rejected(self, acl_agent):
        agent, _mgmt, weak = acl_agent
        for token in ("", weak):
            for path in ("/v1/operator/traces",
                         "/v1/operator/slow-evals"):
                with pytest.raises(urllib.error.HTTPError) as ei:
                    _get(agent.http.addr, path, token=token)
                assert ei.value.code == 403

    def test_management_token_reads_slow_evals(self, acl_agent):
        agent, mgmt, _weak = acl_agent
        status, _, body = _get(agent.http.addr,
                               "/v1/operator/slow-evals", token=mgmt)
        assert status == 200
        data = json.loads(body)
        assert "Trees" in data and "ThresholdMs" in data

    def test_management_token_allowed_and_can_toggle(self, acl_agent):
        agent, mgmt, weak = acl_agent
        status, _, body = _get(agent.http.addr, "/v1/operator/traces",
                               token=mgmt)
        assert status == 200
        # toggle endpoint: management can enable, weak cannot
        req = urllib.request.Request(
            agent.http.addr + "/v1/operator/traces",
            data=json.dumps({"Enable": True}).encode(), method="PUT")
        req.add_header("X-Nomad-Token", mgmt)
        with urllib.request.urlopen(req, timeout=10) as resp:
            assert json.loads(resp.read())["Enabled"] is True
        try:
            req = urllib.request.Request(
                agent.http.addr + "/v1/operator/traces",
                data=json.dumps({"Enable": False}).encode(), method="PUT")
            req.add_header("X-Nomad-Token", weak)
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(req, timeout=10)
            assert ei.value.code == 403
        finally:
            telemetry.disable()
            telemetry.reset()


class TestTraceDecomposition:
    def test_traced_burst_attributes_90_percent(self, tmp_path):
        """The acceptance criterion: the live e2e bench path with
        tracing on emits TRACE_DECOMP.json attributing >= 90% of
        the burst to named spans (CPU backend): of its threads' CPU
        (``cpu_coverage``), and of the median evaluation's wall
        (``tail.p50_coverage``).

        Runs bench/trace_report.py in a SUBPROCESS — the bench's own
        shape. In-suite, ~550 earlier tests leave daemon threads
        whose GIL slices stretch the burst wall without touching the
        system's attributed CPU; a clean process measures the system,
        not the suite's thread leakage. One retry for CI-neighbor
        contention.
        """
        import subprocess

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = tmp_path / "TRACE_DECOMP.json"
        decomp = None
        def _plan_group_ok(d):
            size = d["steady_state"].get("plan_group_size", 0.0)
            wave = d.get("wave", {})
            wave_avg = wave.get("requests", 0) / max(
                wave.get("launches", 1), 1)
            return size >= 0.8 * 32 or size >= 0.85 * wave_avg

        def covered(d):
            # instrumentation COVERAGE is a question of CPU over CPU:
            # of what the interpreter's threads burned over the burst,
            # the share inside a named span. The raw wall share this
            # gate read before (attributed_raw_s / wall_s) counted
            # device wall that overlaps host work twice: 1.05 to 1.07
            # on bursts whose waves a deadline cut into pieces, 0.81
            # on the same instrumentation once a wave stays whole
            # (PERF.md finding 30-3). Overlap cannot raise this one
            # and a contended host cannot lower it, so it needs no
            # fallback.
            return d["cpu_coverage"]

        for _attempt in range(2):
            # 300 jobs x 3 allocs (not 100 x 5): the share gates divide
            # NAMED work by burst wall/CPU, and on a fast box a
            # 100-eval burst is over in ~0.15s — fixed per-burst
            # overheads (thread wakeups, GC, monitor) then eat >10% of
            # the denominator and the gate measures the box, not the
            # instrumentation. Tripling the eval count at comparable
            # total allocs (900, still inside the 300-node capacity —
            # 5 allocs/job at 300 jobs saturates it and blocks evals)
            # amortizes those fixed costs to noise level.
            proc = subprocess.run(
                [sys.executable, os.path.join(repo, "bench",
                                              "trace_report.py"),
                 str(out), "--nodes", "300", "--jobs", "300",
                 "--allocs-per-job", "3", "--batch", "32",
                 "--warmup-jobs", "16", "--bursts", "2"],
                capture_output=True, timeout=360,
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
            )
            assert proc.returncode == 0, proc.stderr.decode()[-2000:]
            decomp = json.loads(out.read_text())
            ss = decomp["steady_state"]
            sched_ok = (ss["sched_host_share"] <= 0.60 or sum(
                decomp["stages"].get(s, {}).get("per_eval_ms", 0.0)
                for s in ("sched-host", "sched-reconcile",
                          "sched-feasibility", "sched-assembly",
                          "sched-planbuild")) <= 3.0)
            tail = decomp.get("tail", {})
            tail_ok = (
                tail.get("histogram", {}).get("count")
                == tail.get("committed_evals")
                and tail.get("p50_coverage", 0.0) >= 0.90)
            if covered(decomp) >= 0.9 \
                    and ss["jit_cache_misses"] == 0 \
                    and decomp["allocs_placed"] == decomp["allocs_wanted"] \
                    and sched_ok \
                    and tail_ok \
                    and _plan_group_ok(decomp) \
                    and (ss["h2d_share"] <= 0.10 or ss["h2d_bytes"]
                         <= 50_000 * decomp["n_evals"]):
                break
        assert decomp["allocs_placed"] == decomp["allocs_wanted"]
        assert covered(decomp) >= 0.9, {
            k: decomp[k] for k in (
                "cpu_coverage", "named_cpu_s", "python_cpu_s", "wall_s",
                "attributed_raw_s", "attributed_share_busy")}
        for stage in ("dequeue", "snapshot", "sched-host",
                      "wave-assembly", "h2d", "execute", "d2h",
                      "plan-apply", "fsm"):
            assert stage in decomp["stages"], stage
        assert "plan-submit" in decomp["overlapped"]
        assert decomp["kernel"]["Launches"] >= 1
        # the 2-burst history separates the compile transient from the
        # steady state the artifact reports
        assert len(decomp["all_bursts"]) == 2
        # ISSUE 2 steady-state gates: with AOT warmup in front, the
        # second burst is compile-free (the regression artifact for
        # compile share) and the dedupe keeps shares within wall
        assert decomp["steady_state"]["jit_cache_misses"] == 0, \
            decomp["kernel"]["PerKey"]
        assert decomp["steady_state"]["compile_share"] < 0.10
        # ISSUE 3 steady gate: with the device-resident cluster state
        # in front of the wave launcher, per-wave h2d is dirty rows +
        # genuinely per-eval planes — its share of steady wall must
        # stay under 10% (was 30.4% when every wave re-uploaded the
        # full shared planes). The share is wall-clocked, so a
        # contended host (GIL theft stretching the firing thread's
        # spans) can inflate it with time the transfer never used; the
        # steal-invariant fallback is the BYTE meter — re-uploading
        # full planes per wave costs >100KB/eval, residency ~10-40KB —
        # which is a property of the system, not of the CI neighbors.
        ss = decomp["steady_state"]
        assert ss["h2d_share"] <= 0.10 \
            or ss["h2d_bytes"] <= 50_000 * decomp["n_evals"], ss
        # and the transfer byte meters actually metered
        assert ss["h2d_bytes"] > 0
        assert ss["d2h_bytes"] > 0
        assert decomp["attributed_share"] <= 1.0
        # wave-shape telemetry rides the artifact
        assert decomp["wave"]["launches"] >= 1
        assert 0.0 < decomp["wave"]["fill_ratio"] <= 1.0
        # device-residency accounting rides it too: the steady burst
        # must be advancing by dirty-row scatter, not full re-uploads
        assert decomp["device_state"]["delta_advances"] >= 1, \
            decomp["device_state"]
        # One-device steady gates (ISSUE 31): every steady wave is the
        # program a TPU runs, ``joint``, with its eager result fetch:
        # two wave-critical device interactions a wave (the deferred
        # top-k drain is excluded by definition), none of them a
        # compile (jit_cache_misses == 0 above), and the mesh's fused
        # counters stand still.
        disp = decomp["kernel"].get("Dispatches", {})
        assert disp.get("joint", 0) == decomp["wave"]["launches"] > 0, (
            disp, decomp["wave"])
        assert disp.get("wave_fetch", 0) == disp["joint"], disp
        assert "fused_wave" not in disp, disp
        assert ss["dispatches_per_wave"] == 2.0, (ss, disp)
        assert ss["fused_sharded_launches"] == 0, decomp.get("wave_fused")
        assert ss["fused_sharded_fallbacks"] == 0, decomp.get("wave_fused")
        # ISSUE 5 steady gates. sched_host_share sums the
        # eval.schedule residue + the feasibility/assembly/plan-build
        # sub-slices. Post-compiler, the feasibility slice itself is
        # a cache lookup (hit ratio gated below); what remains is the
        # GIL-bound floor of the Go-parity scheduler Python (~2.4
        # ms/eval: reconcile, option/assign, plan build) — on the CPU
        # backend, where wall per eval IS that Python, the share
        # bottoms out near 0.30 at 150+ evals/s (it was 0.52 before
        # the compiler + the tracer's clock-syscall bias fix; docs/
        # PERF.md "The feasibility compiler"). The share's numerator
        # is thread CPU, so host contention stretches the wall
        # denominator and can only shrink it — the steal-invariant
        # fallback bound is the per-eval CPU milliseconds of the same
        # four slices. The share bound is derived from ``joint``
        # bursts (ISSUE 31; CHANGES.md has the runs): a healthy
        # scheduler reads 0.44 to 0.47 of the burst's wall on a quiet
        # box, the parent's interpreted fused program read the same
        # (0.46 to 0.47), and the bound stands a quarter above the
        # largest reading.
        sched_ms = sum(
            decomp["stages"].get(s, {}).get("per_eval_ms", 0.0)
            for s in ("sched-host", "sched-reconcile",
                      "sched-feasibility", "sched-assembly",
                      "sched-planbuild"))
        assert ss["sched_host_share"] <= 0.60 or sched_ms <= 3.0, \
            (ss["sched_host_share"], sched_ms)
        # ISSUE 10: the reconcile slice is spanned on its own (the
        # fused single-pass classifier's trajectory line)
        assert "sched-reconcile" in decomp["stages"]
        assert "reconcile_share" in ss
        # steady traffic re-uses compiled masks: misses only on node
        # structure forks and novel job specs, never per eval
        assert ss["feasibility_hit_ratio"] >= 0.95, \
            decomp.get("feasibility")
        # ISSUE 6 steady gates: the group-commit pass must prove EVERY
        # plan of the lean burst from the utilization planes — a
        # fallback means the vectorized check silently lost coverage
        # (the exact walk is bit-identical, so only this counter ever
        # reveals the regression) — and the plan-path share is
        # surfaced so the next re-anchor has a trajectory line
        assert ss["plan_group_fallbacks"] == 0, decomp.get("plan_group")
        assert decomp.get("plan_group", {}).get("plans", 0) > 0, \
            decomp.get("plan_group")
        assert "plan_share" in ss
        # batched raft entries actually batch when plans queue up; a
        # serialized applier would pin this at exactly 1.0 (tolerate
        # a trickle-paced burst, but the counter must exist and move)
        assert decomp.get("plan_group", {}).get("commit_batches", 0) > 0
        # ISSUE 10 wave-boundary gate: with the plan queue's drain
        # window armed per wave cohort, a wave's plans commit as ~ONE
        # raft entry — plans per entry must reach 0.8x the worker
        # batch size (the burst runs --batch 32; was ~5.6 before).
        # Steal-tolerant fallback: under CI-neighbor/parent-suite
        # contention the INGEST fragments waves themselves; the
        # mechanism's property is then "the applier commits whole
        # waves", i.e. plans-per-entry tracks the average wave size.
        assert _plan_group_ok(decomp), \
            (decomp.get("plan_group"), decomp.get("wave"))
        # ISSUE 8 tail gates: the tail section exists; every committed
        # eval of the burst landed in the e2e histogram (count
        # equality — no eval escapes the distribution); and the named
        # waterfall segments explain >= 90% of the median cohort's
        # e2e latency (dequeue-wait/snapshot/schedule/park/launch/
        # plan-queue/evaluate/commit/fsm — "other" never counts
        # toward coverage)
        tail = decomp["tail"]
        assert tail["committed_evals"] > 0
        assert tail["histogram"]["count"] == tail["committed_evals"], \
            (tail["histogram"], tail["committed_evals"])
        assert not tail["ring_wrapped"]
        # every committed eval also produced a waterfall (the e2e
        # marker span anchors it)
        assert tail["e2e_count"] == tail["committed_evals"]
        assert tail["p50_coverage"] >= 0.90, tail
        assert tail["segments"], tail
        # the p50-vs-p99 table carries both cohorts for each segment
        for seg, row in tail["segments"].items():
            assert {"p50_ms", "p50_share", "p99_ms", "p99_share"} \
                <= set(row), (seg, row)
        # the distribution rides into steady_state for bench emission
        assert ss["e2e_p99_ms"] >= ss["e2e_p50_ms"] > 0.0
        # the flight recorder observed the burst (captures depend on
        # the distribution's shape; observation must not)
        assert tail["flight_recorder"]["observed"] == \
            tail["committed_evals"]
        # ISSUE 11: the serving section rides the artifact — even a
        # burst with no external subscribers publishes every FSM apply
        # into the event ring, so the publish/watch/heartbeat counters
        # must exist and the ring must have seen the burst's applies
        serving = decomp["serving"]
        assert serving["stream"]["published_events"] > 0, serving
        assert serving["stream"]["lost_events"] == 0
        for section, keys in (
            ("stream", ("subscribers", "published_events",
                        "delivered_events", "lost_events",
                        "max_lag_events", "delivered_bytes")),
            ("watch", ("held_watchers", "wakeups", "spurious_wakeups",
                       "timeouts")),
            ("heartbeat", ("heartbeats", "callers", "batches",
                           "coalesce_ratio")),
        ):
            assert set(keys) <= set(serving[section]), (
                section, serving[section])
        assert "deliver_latency" in serving

    def test_mesh_steady_burst_gates_sharded_keys(self, tmp_path):
        """ISSUE 14 steady gates: with the device mesh on (the
        conftest 8-virtual-CPU mesh via use_device_mesh=True), the
        steady burst's TRACE_DECOMP steady_state must report every
        wave dispatched SHARDED (launches > 0), ZERO single-device
        fallbacks, and — like the unsharded burst — zero jit cache
        misses on the second (steady) burst: the AOT warmup learned
        the sharded signatures. Subprocess for the same reason as the
        main decomposition test (a clean process measures the system);
        smaller shape — the perf share gates stay with the unsharded
        artifact, this one gates the sharding plumbing."""
        import subprocess

        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        out = tmp_path / "TRACE_DECOMP_MESH.json"
        proc = subprocess.run(
            [sys.executable, os.path.join(repo, "bench",
                                          "trace_report.py"),
             str(out), "--nodes", "200", "--jobs", "96",
             "--allocs-per-job", "3", "--batch", "16",
             "--warmup-jobs", "10", "--bursts", "2", "--mesh"],
            capture_output=True, timeout=360,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode == 0, proc.stderr.decode()[-2000:]
        decomp = json.loads(out.read_text())
        assert decomp["allocs_placed"] == decomp["allocs_wanted"]
        ss = decomp["steady_state"]
        # the new steady keys exist and hold: sharded is THE path on a
        # mesh server (fallbacks would mean single-device dispatches
        # leaked into the steady state)
        assert ss["mesh_devices"] == 8, ss
        assert ss["sharded_wave_launches"] > 0, ss
        assert ss["sharded_wave_launches"] == \
            decomp["wave"]["launches"], (ss, decomp["wave"])
        assert ss["sharded_wave_fallbacks"] == 0, ss
        # steady-state compile discipline holds under sharding too
        assert ss["jit_cache_misses"] == 0, \
            decomp["kernel"]["PerKey"]
        # group-commit health is dispatch-independent
        assert ss["plan_group_fallbacks"] == 0, decomp.get("plan_group")
        # the resident state advanced sharded between waves
        assert decomp["device_state"]["delta_advances"] >= 1, \
            decomp["device_state"]
        # ISSUE 19: a mesh's lean waves run FUSED
        # (fused_wave_sharded), at one dispatch per wave
        assert ss["fused_sharded_fallbacks"] == 0, ss
        assert ss["fused_sharded_launches"] == \
            decomp["wave"]["launches"], (ss, decomp["wave"])
        assert ss["dispatches_per_wave"] == 1.0, (
            ss, decomp["kernel"].get("Dispatches"))

    def test_disabled_tracing_leaves_no_spans(self):
        """The disabled live path must record nothing (the <5%
        overhead claim rests on the no-op fast path actually being
        taken everywhere)."""
        telemetry.disable()
        telemetry.reset()
        from nomad_tpu import mock
        from nomad_tpu.server.server import Server, ServerConfig

        server = Server(ServerConfig(num_workers=1, worker_batch_size=4,
                                     heartbeat_ttl=3600.0))
        server.start()
        try:
            for _ in range(10):
                server.node_register(mock.node())
            job = mock.simple_job()
            job.task_groups[0].count = 4
            server.job_register(job)
            deadline = time.time() + 60
            while time.time() < deadline:
                snap = server.state.snapshot()
                if len(snap.allocs_by_job(job.namespace, job.id)) >= 4:
                    break
                time.sleep(0.05)
            assert tracer.stage_totals() == {}
            assert profiler.summary()["Launches"] == 0
        finally:
            server.shutdown()


class TestMVCCStoreTelemetry:
    """ISSUE 16: the MVCC store's telemetry surface — the store_*
    Prometheus series, and the lock-free-reads proof: under the lock
    witness, a read storm records ZERO store-lock hold samples while
    write transactions record on lock_hold_store_write_txn."""

    def test_store_series_exported(self, clean_telemetry):
        from nomad_tpu import mock
        from nomad_tpu.state.store import StateStore

        store = StateStore()
        store.upsert_node(mock.node())
        store.snapshot()
        text = prometheus_text()
        assert "# TYPE nomad_tpu_store_write_txns_total counter" in text
        assert "nomad_tpu_store_snapshots_total" in text
        assert "nomad_tpu_store_restores_total" in text
        assert "nomad_tpu_store_generation" in text
        assert "nomad_tpu_store_live_roots" in text

    def test_read_path_holds_no_store_lock(self):
        from nomad_tpu import mock
        from nomad_tpu.state.store import StateStore
        from nomad_tpu.telemetry.histogram import histograms
        from nomad_tpu.utils import witness

        witness.reset()
        witness.enable()
        try:
            # the witness wraps locks created AFTER enable(): this
            # store's write/watch locks feed lock_hold_* histograms
            store = StateStore()
            nodes = [mock.node() for _ in range(20)]
            for n in nodes:
                store.upsert_node(n)

            def holds(name):
                h = histograms.peek(f"lock_hold_{name}")
                return h.count if h is not None else 0

            write_holds = holds("store_write_txn")
            assert write_holds >= 20  # every txn records its hold

            # the read storm: snapshots, row reads, direct readers,
            # scoped views — none may touch a store lock
            before_txn = holds("store_write_txn")
            before_watch = holds("store_watch")
            for _ in range(200):
                snap = store.snapshot()
                snap.node_by_id(nodes[0].id)
                snap.nodes()
                store.node_by_id_direct(nodes[-1].id)
                store.allocs_by_node_direct(nodes[0].id)
                store.has_draining_nodes()
                store.latest_index()
                store.with_usage_view(lambda planes, allocs: None)
            assert holds("store_write_txn") == before_txn
            assert holds("store_watch") == before_watch
        finally:
            assert witness.violations() == []
            witness.disable()
            witness.reset()

    def test_write_txn_histogram_always_records(self, clean_telemetry):
        """store_write_txn latency records per commit with or without
        the witness — it is the store's own instrumentation, not the
        witness's."""
        from nomad_tpu import mock
        from nomad_tpu.state.store import StateStore
        from nomad_tpu.telemetry.histogram import histograms

        before = histograms.get("store_write_txn").count
        store = StateStore()
        store.upsert_node(mock.node())
        store.upsert_node(mock.node())
        assert histograms.get("store_write_txn").count == before + 2
