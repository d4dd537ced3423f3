"""Kernel golden-parity tests.

An independent pure-Python oracle reproduces the Go iterator semantics
(feasible.go / rank.go / spread.go / select.go MaxScore) with float64
math; the JAX kernel must match its choices exactly and its scores to
float32 tolerance. This is the port of the reference's scheduler unit
tests' role (rank_test.go, spread_test.go) onto the batched formulation.
"""

import functools
import math

import numpy as np
import pytest

from nomad_tpu.ops.kernel import KernelOut, build_kernel_in, pad_steps, place_taskgroup_jit
from nomad_tpu.tensors.schema import (
    MAX_DEV_REQS,
    SPREAD_BUCKETS,
    AskTensor,
    ClusterTensors,
    EvalTensors,
    SpreadTensor,
    pad_bucket,
)


# ---------------------------------------------------------------------------
# Helpers to build small synthetic clusters without full structs
# ---------------------------------------------------------------------------


def make_cluster(caps):
    """caps: list of (cpu, mem) tuples."""
    n = len(caps)
    npad = pad_bucket(n)
    c = ClusterTensors(
        n_real=n,
        n_pad=npad,
        node_ids=[f"node-{i}" for i in range(n)],
        index={f"node-{i}": i for i in range(n)},
        cap_cpu=np.zeros(npad, np.float32),
        cap_mem=np.zeros(npad, np.float32),
        cap_disk=np.full(npad, 1 << 20, np.float32),
        ready=np.zeros(npad, bool),
        port_words=np.zeros((npad, 2048), np.uint32),
        free_dyn=np.full(npad, 12001, np.int32),
        free_cores=np.full(npad, 8, np.int32),
        shares_per_core=np.full(npad, 1000.0, np.float32),
        datacenters=["dc1"] * n,
        node_classes=[""] * n,
        computed_classes=["c0"] * n,
        node_pools=["default"] * n,
    )
    for i, (cpu, mem) in enumerate(caps):
        c.cap_cpu[i] = cpu
        c.cap_mem[i] = mem
        c.ready[i] = True
    return c


def make_eval(cluster, ask=None, **kw):
    n = cluster.n_pad
    base = np.zeros(n, bool)
    base[: cluster.n_real] = True
    ev = EvalTensors(
        base_mask=kw.get("base_mask", base),
        used_cpu=kw.get("used_cpu", np.zeros(n, np.float32)),
        used_mem=kw.get("used_mem", np.zeros(n, np.float32)),
        used_disk=np.zeros(n, np.float32),
        used_mbits=np.zeros(n, np.int32),
        avail_mbits=np.full(n, 1000, np.int32),
        used_cores=np.zeros(n, np.int32),
        port_conflict_words=np.zeros((n, 2048), np.uint32),
        free_dyn_delta=np.zeros(n, np.int32),
        dev_free=kw.get("dev_free", np.zeros((n, MAX_DEV_REQS), np.float32)),
        dev_aff_score=kw.get("dev_aff_score", np.zeros(n, np.float32)),
        has_dev_affinity=kw.get("has_dev_affinity", False),
        job_tg_count=kw.get("job_tg_count", np.zeros(n, np.int32)),
        job_any_count=kw.get("job_any_count", np.zeros(n, np.int32)),
        distinct_hosts_job=kw.get("distinct_hosts_job", False),
        distinct_hosts_tg=kw.get("distinct_hosts_tg", False),
        penalty=kw.get("penalty", np.zeros(n, bool)),
        aff_score=kw.get("aff_score", np.zeros(n, np.float32)),
        has_affinities=bool(np.any(kw.get("aff_score", np.zeros(1)) != 0)),
        spreads=kw.get("spreads", []),
        ask=ask or AskTensor.build_from_simple(),
        desired_count=kw.get("desired_count", 1),
        algorithm=kw.get("algorithm", "binpack"),
    )
    return ev


def simple_ask(cpu=500, mem=256, disk=0, dyn=0, dev=None):
    a = AskTensor()
    a.cpu, a.mem, a.disk = float(cpu), float(mem), float(disk)
    a.n_dyn_ports = dyn
    a.reserved_ports = []
    a.port_mask = np.zeros(2048, np.uint32)
    a.dev_counts = np.zeros(MAX_DEV_REQS, np.int32)
    if dev:
        for i, d in enumerate(dev):
            a.dev_counts[i] = d
    return a


AskTensor.build_from_simple = staticmethod(simple_ask)


def run_kernel(cluster, ev, k):
    kin = build_kernel_in(cluster, ev, k)
    out = place_taskgroup_jit(kin, pad_steps(k))
    return KernelOut(*[np.asarray(x) for x in out])


# ---------------------------------------------------------------------------
# The float64 oracle (Go semantics)
# ---------------------------------------------------------------------------


def oracle_place(cluster, ev, k):
    """Sequential max-score placement with Go's scoring rules."""
    n = cluster.n_real
    used_cpu = ev.used_cpu.astype(np.float64).copy()
    used_mem = ev.used_mem.astype(np.float64).copy()
    job_cnt = ev.job_tg_count.astype(np.int64).copy()
    dev_free = ev.dev_free.astype(np.float64).copy()
    free_dyn = (cluster.free_dyn - ev.free_dyn_delta).astype(np.int64).copy()
    sp_counts = [s.counts.astype(np.float64).copy() for s in ev.spreads]
    results = []
    ask = ev.ask
    for _ in range(k):
        best_i, best_s = -1, None
        for i in range(n):
            if not ev.base_mask[i]:
                continue
            cap_c, cap_m = cluster.cap_cpu[i], cluster.cap_mem[i]
            if cap_c - used_cpu[i] < ask.cpu or cap_m - used_mem[i] < ask.mem:
                continue
            if free_dyn[i] < ask.n_dyn_ports:
                continue
            if np.any(dev_free[i] < ask.dev_counts):
                continue
            util_c, util_m = used_cpu[i] + ask.cpu, used_mem[i] + ask.mem
            fc = 1 - util_c / cap_c if cap_c > 0 else 0.0
            fm = 1 - util_m / cap_m if cap_m > 0 else 0.0
            total = 10.0 ** fc + 10.0 ** fm
            if ev.algorithm == "spread":
                raw = min(max(total - 2.0, 0.0), 18.0)
            else:
                raw = min(max(20.0 - total, 0.0), 18.0)
            scores = [raw / 18.0]
            if ev.has_dev_affinity:
                scores.append(float(ev.dev_aff_score[i]))
            col = int(job_cnt[i])
            if col > 0:
                scores.append(-(col + 1) / max(ev.desired_count, 1))
            if ev.penalty[i]:
                scores.append(-1.0)
            if ev.aff_score[i] != 0.0:
                scores.append(float(ev.aff_score[i]))
            sp_total = 0.0
            for s_i, sp in enumerate(ev.spreads):
                b = int(sp.bucket_id[i])
                if b < 0:
                    sp_total += -1.0
                    continue
                cnt = sp_counts[s_i][b]
                if sp.even:
                    counts = sp_counts[s_i]
                    present = counts > 0
                    if not present.any():
                        continue
                    minc = counts[present].min()
                    maxc = counts[present].max()
                    if cnt != minc:
                        sp_total += (minc - cnt) / minc if minc > 0 else -1.0
                    elif minc == maxc:
                        sp_total += -1.0
                    elif minc == 0:
                        sp_total += 1.0
                    else:
                        sp_total += (maxc - minc) / minc
                else:
                    des = sp.desired[b]
                    if des > 0:
                        sp_total += ((des - (cnt + 1)) / des) * sp.weight_frac
                    else:
                        sp_total += -1.0
            if sp_total != 0.0:
                scores.append(sp_total)
            final = sum(scores) / len(scores)
            if best_s is None or final > best_s:
                best_i, best_s = i, final
        if best_i < 0:
            results.append((-1, 0.0))
            continue
        results.append((best_i, best_s))
        used_cpu[best_i] += ask.cpu
        used_mem[best_i] += ask.mem
        job_cnt[best_i] += 1
        dev_free[best_i] -= ask.dev_counts
        free_dyn[best_i] -= ask.n_dyn_ports
        for s_i, sp in enumerate(ev.spreads):
            b = int(sp.bucket_id[best_i])
            if b >= 0:
                sp_counts[s_i][b] += 1
    return results


def assert_parity(cluster, ev, k):
    out = run_kernel(cluster, ev, k)
    want = oracle_place(cluster, ev, k)
    for step, (wi, ws) in enumerate(want):
        assert out.chosen[step] == wi, (
            f"step {step}: kernel chose {out.chosen[step]}, oracle {wi} "
            f"(kernel score {out.scores[step]}, oracle {ws})"
        )
        if wi >= 0:
            assert out.scores[step] == pytest.approx(ws, abs=2e-5)
    return out


# ---------------------------------------------------------------------------
# Tests
# ---------------------------------------------------------------------------


class TestBinpackScoring:
    def test_picks_most_packed_feasible(self):
        # binpack prefers the node that ends up most utilized
        cluster = make_cluster([(4000, 8192), (4000, 8192), (4000, 8192)])
        used = np.zeros(cluster.n_pad, np.float32)
        used[1] = 2000  # node 1 is half full on cpu
        ev = make_eval(cluster, ask=simple_ask(), used_cpu=used)
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 1

    def test_score_matches_structs_math(self):
        from nomad_tpu import structs, mock

        cluster = make_cluster([(4000, 8192)])
        ev = make_eval(cluster, ask=simple_ask(cpu=2000, mem=4096))
        out = run_kernel(cluster, ev, 1)
        node = mock.node()
        node.node_resources.cpu.cpu_shares = 4000
        node.node_resources.memory.memory_mb = 8192
        node.reserved_resources = structs.NodeReservedResources()
        want = structs.score_fit_binpack(
            node, structs.ComparableResources(cpu_shares=2000, memory_mb=4096)
        ) / 18.0
        assert out.scores[0] == pytest.approx(want, abs=2e-5)  # f32 pow

    def test_spread_algorithm_flips_score(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        used = np.zeros(cluster.n_pad, np.float32)
        used[0] = 2000
        ev = make_eval(cluster, ask=simple_ask(), used_cpu=used, algorithm="spread")
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 1  # worst-fit prefers the empty node

    def test_infeasible_all(self):
        cluster = make_cluster([(400, 512)])
        ev = make_eval(cluster, ask=simple_ask(cpu=500, mem=256))
        out = run_kernel(cluster, ev, 1)
        assert out.chosen[0] == -1
        assert not out.found[0]
        assert out.exhausted_cpu == 1


class TestSequentialDeduction:
    def test_resources_deducted_between_placements(self):
        # one node fits exactly two asks; third placement must go elsewhere
        cluster = make_cluster([(1000, 1024), (4000, 8192)])
        used = np.zeros(cluster.n_pad, np.float32)
        used[1] = 3000  # node 1 more packed -> preferred until full
        ev = make_eval(cluster, ask=simple_ask(cpu=500, mem=256), used_cpu=used)
        assert_parity(cluster, ev, 5)

    def test_exhaustion_mid_sequence(self):
        cluster = make_cluster([(1000, 512), (1000, 512)])
        ev = make_eval(cluster, ask=simple_ask(cpu=400, mem=200))
        out = assert_parity(cluster, ev, 5)
        # 2 per node fit (400*2=800<1000, 200*2=400<512), 5th fails
        assert list(out.found[:5]) == [True, True, True, True, False]


class TestAntiAffinity:
    def test_collision_penalty(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        cnt = np.zeros(cluster.n_pad, np.int32)
        cnt[0] = 2  # node 0 already has 2 allocs of this job/tg
        ev = make_eval(
            cluster, ask=simple_ask(), job_tg_count=cnt, desired_count=10
        )
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 1

    def test_spreads_across_nodes(self):
        # with anti-affinity via job_tg_count updates, placements alternate
        cluster = make_cluster([(8000, 16384), (8000, 16384)])
        ev = make_eval(cluster, ask=simple_ask(), desired_count=4)
        out = assert_parity(cluster, ev, 4)
        assert sorted(np.bincount(out.chosen[:4], minlength=2)[:2].tolist()) == [2, 2]


class TestPenaltyAndAffinity:
    def test_reschedule_penalty(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        pen = np.zeros(cluster.n_pad, bool)
        pen[0] = True
        ev = make_eval(cluster, ask=simple_ask(), penalty=pen)
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 1

    def test_node_affinity_attracts(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        aff = np.zeros(cluster.n_pad, np.float32)
        aff[0] = 0.8
        ev = make_eval(cluster, ask=simple_ask(), aff_score=aff)
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 0

    def test_negative_affinity_repels(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        aff = np.zeros(cluster.n_pad, np.float32)
        aff[0] = -0.5
        ev = make_eval(cluster, ask=simple_ask(), aff_score=aff)
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 1


def spread_tensor(n_pad, buckets, counts=(), desired=None, weight=1.0,
                  even=False):
    """A stanza over the first len(buckets) nodes; -1 marks a node that
    lacks the attribute, and every node past them."""
    b = np.full(n_pad, -1, np.int32)
    b[: len(buckets)] = buckets
    c = np.zeros(SPREAD_BUCKETS, np.float32)
    c[: len(counts)] = counts
    d = np.full(SPREAD_BUCKETS, -1.0, np.float32)
    if desired is not None:
        d[: len(desired)] = desired
    return SpreadTensor(bucket_id=b, counts=c, desired=d,
                        weight_frac=weight, even=even)


class TestSpreadStanza:
    def _spread(self, cluster, buckets, counts, desired, weight=1.0, even=False):
        return spread_tensor(cluster.n_pad, buckets, counts, desired,
                             weight, even)

    def test_desired_count_spread(self):
        # 4 nodes: dc0,dc0,dc1,dc1; desire 3 in dc0, 1 in dc1 (count 4)
        cluster = make_cluster([(4000, 8192)] * 4)
        sp = self._spread(
            cluster, buckets=[0, 0, 1, 1], counts=[0, 0], desired=[3.0, 1.0]
        )
        ev = make_eval(cluster, ask=simple_ask(), spreads=[sp], desired_count=4)
        out = assert_parity(cluster, ev, 4)
        placed = out.chosen[:4]
        dc0 = sum(1 for i in placed if i in (0, 1))
        assert dc0 == 3  # 3 of 4 land in dc0

    def test_even_spread(self):
        cluster = make_cluster([(8000, 16384)] * 4)
        sp = self._spread(
            cluster, buckets=[0, 0, 1, 1], counts=[2, 0], desired=None, even=True
        )
        ev = make_eval(cluster, ask=simple_ask(), spreads=[sp], desired_count=2)
        out = assert_parity(cluster, ev, 2)
        # bucket 1 has fewer allocs -> both placements favor nodes 2,3
        assert set(out.chosen[:2].tolist()) == {2, 3}

    def test_missing_attribute_penalized(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        b = np.full(cluster.n_pad, -1, np.int32)
        b[0] = 0  # node 1 lacks the attribute
        sp = SpreadTensor(
            bucket_id=b,
            counts=np.zeros(SPREAD_BUCKETS, np.float32),
            desired=np.full(SPREAD_BUCKETS, -1.0, np.float32),
            weight_frac=1.0,
            even=True,
        )
        ev = make_eval(cluster, ask=simple_ask(), spreads=[sp])
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 0


class TestPortsAndDevices:
    def test_reserved_port_conflict(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        # node 0 has port 8080 in use
        cluster.port_words[0, 8080 // 32] |= np.uint32(1 << (8080 % 32))
        ask = simple_ask()
        ask.reserved_ports.append(8080)
        ask.port_mask[8080 // 32] |= np.uint32(1 << (8080 % 32))
        ev = make_eval(cluster, ask=ask)
        out = run_kernel(cluster, ev, 2)
        assert out.chosen[0] == 1
        # second placement of same group also needs 8080 -> node 1 now
        # conflicts with itself -> no placement
        assert out.chosen[1] == -1
        assert out.exhausted_ports >= 1

    def test_dynamic_port_exhaustion(self):
        cluster = make_cluster([(4000, 8192)])
        cluster.free_dyn[0] = 1
        ev = make_eval(cluster, ask=simple_ask(dyn=2))
        out = run_kernel(cluster, ev, 1)
        assert out.chosen[0] == -1

    def test_device_fit_and_deduction(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        dev = np.zeros((cluster.n_pad, MAX_DEV_REQS), np.float32)
        dev[0, 0] = 2  # node 0 has 2 GPUs free
        dev[1, 0] = 1
        ev = make_eval(cluster, ask=simple_ask(dev=[1]), dev_free=dev)
        out = assert_parity(cluster, ev, 3)
        # 3 placements: two on node 0, one on node 1 (order per scoring)
        assert sorted(out.chosen[:3].tolist()) == [0, 0, 1]
        assert bool(out.found[2])

    def test_device_affinity_plane(self):
        cluster = make_cluster([(4000, 8192), (4000, 8192)])
        dev = np.ones((cluster.n_pad, MAX_DEV_REQS), np.float32)
        daff = np.zeros(cluster.n_pad, np.float32)
        daff[1] = 0.9
        ev = make_eval(
            cluster, ask=simple_ask(dev=[1]), dev_free=dev,
            dev_aff_score=daff, has_dev_affinity=True,
        )
        out = assert_parity(cluster, ev, 1)
        assert out.chosen[0] == 1


class TestMetrics:
    def test_counts(self):
        cluster = make_cluster([(4000, 8192), (400, 128), (4000, 8192)])
        base = np.zeros(cluster.n_pad, bool)
        base[:3] = True
        base[2] = False  # class-filtered
        ev = make_eval(cluster, ask=simple_ask(), base_mask=base)
        out = run_kernel(cluster, ev, 1)
        assert out.nodes_evaluated == 2
        assert out.nodes_feasible == 1
        assert out.exhausted_cpu == 1
        assert out.exhausted_mem == 1


class TestStepPadding:
    def test_padded_steps_inactive(self):
        cluster = make_cluster([(8000, 16384)])
        ev = make_eval(cluster, ask=simple_ask())
        kin = build_kernel_in(cluster, ev, 3)
        out = place_taskgroup_jit(kin, pad_steps(3))  # pads to 4
        out = KernelOut(*[np.asarray(x) for x in out])
        assert list(out.found[:3]) == [True, True, True]
        assert not out.found[3]  # padded step places nothing

    def test_pad_steps_buckets(self):
        assert pad_steps(1) == 1
        assert pad_steps(3) == 4
        assert pad_steps(100) == 128
        assert pad_steps(5000) == 8192


class TestKernelFeatures:
    """Static specialization must not change semantics when the
    disabled features' inputs are neutral."""

    def test_lean_matches_full(self):
        import numpy as np

        from nomad_tpu.ops.kernel import (
            FULL_FEATURES,
            KernelFeatures,
            KernelOut,
            place_taskgroup_jit,
        )
        from nomad_tpu.parallel.synthetic import synthetic_kernel_in

        kin = synthetic_kernel_in(n_nodes=100, n_steps=8, used_frac=0.5)
        lean = KernelFeatures(
            n_spreads=0, with_topk=False, with_devices=False,
            with_ports=False, with_cores=False, with_network=False,
            with_distinct=False, with_step_penalties=False,
            with_preferred=False,
        )
        full = KernelOut(*[np.asarray(x) for x in place_taskgroup_jit(kin, 8, FULL_FEATURES)])
        got = KernelOut(*[np.asarray(x) for x in place_taskgroup_jit(kin, 8, lean)])
        np.testing.assert_array_equal(got.chosen, full.chosen)
        np.testing.assert_array_equal(got.found, full.found)
        np.testing.assert_allclose(got.scores, full.scores, rtol=1e-6)

    def test_spread_specialization(self):
        import numpy as np

        from nomad_tpu.ops.kernel import (
            FULL_FEATURES,
            KernelOut,
            infer_features,
            place_taskgroup_jit,
        )
        from nomad_tpu.ops.kernel import build_kernel_in
        from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

        cluster = synthetic_cluster(100, seed=3)
        ev = synthetic_eval(cluster, with_spread=True, used_frac=0.3, seed=3)
        kin = build_kernel_in(cluster, ev, 8)
        feats = infer_features(ev)
        assert feats.n_spreads == 1
        full = KernelOut(*[np.asarray(x) for x in place_taskgroup_jit(kin, 8, FULL_FEATURES)])
        got = KernelOut(*[np.asarray(x) for x in place_taskgroup_jit(kin, 8, feats)])
        np.testing.assert_array_equal(got.chosen, full.chosen)
        np.testing.assert_allclose(got.scores, full.scores, rtol=1e-6)


class TestCandidateKernel:
    """place_taskgroup_topk: candidate-set placement must be exact.

    The bound argument: every score-mutating plane moves non-chosen
    nodes down or not at all (no spreads), so the max over
    non-candidates is a standing upper bound; the kernel flags
    ``valid=False`` whenever a step's choice falls below it.
    """

    def _kin(self, rng, n, with_extras=False):
        import numpy as np

        from nomad_tpu.ops.kernel import build_kernel_in
        from nomad_tpu.parallel.synthetic import (
            synthetic_cluster, synthetic_eval,
        )

        cluster = synthetic_cluster(
            n, cpu=3900.0, mem=7936.0, disk=98304.0,
            seed=int(rng.integers(0, 99)))
        ev = synthetic_eval(cluster, desired_count=10)
        kwargs = {}
        if with_extras:
            pen = np.full((16, 4), -1, np.int32)
            pen[0, 0] = rng.integers(0, n)
            pref = np.full(16, -1, np.int32)
            pref[2] = rng.integers(0, n)
            kwargs = dict(
                step_penalty=pen, step_preferred=pref,
                node_perm=rng.permutation(cluster.n_pad).astype(np.int32),
            )
        kin = build_kernel_in(cluster, ev, 10, **kwargs)
        uc = (3900 * 0.7 * rng.random(cluster.n_pad)).astype(np.float32)
        um = (7936 * 0.7 * rng.random(cluster.n_pad)).astype(np.float32)
        return kin._replace(
            used_cpu=uc, used_mem=um,
            ask_cpu=np.float32(rng.choice([250, 500, 900])),
            ask_mem=np.float32(rng.choice([128, 256, 700])),
        )

    def test_matches_full_kernel(self):
        import numpy as np

        from nomad_tpu.ops.kernel import (
            LEAN_FEATURES, pad_steps, place_taskgroup_jit,
            place_taskgroup_topk_jit,
        )

        rng = np.random.default_rng(17)
        feats_variants = [
            (LEAN_FEATURES, False),
            (LEAN_FEATURES._replace(with_topk=True, with_distinct=True),
             False),
            (LEAN_FEATURES._replace(
                with_step_penalties=True, with_preferred=True,
                with_shuffle=True), True),
        ]
        k = pad_steps(10)
        for trial in range(6):
            feats, extras = feats_variants[trial % 3]
            kin = self._kin(rng, int(rng.choice([60, 400])), extras)
            full = place_taskgroup_jit(kin, k, feats)
            topk, ok = place_taskgroup_topk_jit(kin, k, feats)
            if not bool(ok):
                continue  # bound breached: caller re-runs full kernel
            assert np.array_equal(
                np.asarray(full.chosen), np.asarray(topk.chosen)), trial
            assert np.array_equal(
                np.asarray(full.found), np.asarray(topk.found)), trial
            assert np.allclose(
                np.asarray(full.scores), np.asarray(topk.scores),
                atol=1e-6), trial

    def test_invalid_flag_on_tiny_feasible_set(self):
        """When the cluster nearly saturates, candidates can exhaust;
        the kernel must flag it rather than silently fail placements
        the wider cluster could serve."""
        import numpy as np

        from nomad_tpu.ops.kernel import (
            LEAN_FEATURES, pad_steps, place_taskgroup_jit,
            place_taskgroup_topk_jit,
        )

        rng = np.random.default_rng(3)
        kin = self._kin(rng, 400)
        # leave only a sliver of cpu on every node: ask barely fits
        kin = kin._replace(
            used_cpu=np.full_like(kin.used_cpu, 3900.0 - 510.0),
            ask_cpu=np.float32(500.0),
        )
        k = pad_steps(10)
        full = place_taskgroup_jit(kin, k, LEAN_FEATURES)
        topk, ok = place_taskgroup_topk_jit(kin, k, LEAN_FEATURES)
        if bool(ok):
            assert np.array_equal(
                np.asarray(full.chosen), np.asarray(topk.chosen))
        else:
            # fallback path: full kernel remains the source of truth
            assert np.asarray(full.found).sum() >= np.asarray(topk.found).sum()


# ---------------------------------------------------------------------------
# Spread scoring on the node axis: parity with a plain evaluation by bucket
# table, and the rule that a scan step holds no [node, bucket] value
# ---------------------------------------------------------------------------

SPREAD_N_NODES = 20        # real nodes of the parity cluster (n_pad 64)
SPREAD_STEPS = 48          # steps per member: the carry runs well past 40


def _spread_stanzas(name, rng):
    """(stanzas, spread_active or None) of one wave member for the named
    case, over the parity cluster. ``spread_active`` overrides
    build_kernel_in's "the first len(spreads) stanzas are on"."""
    n = SPREAD_N_NODES
    stanza = functools.partial(spread_tensor, pad_bucket(n))
    racks = np.arange(n) % 5
    dcs = np.arange(n) % 2
    if name == "even":
        return [stanza(racks, even=True)], None
    if name == "desired_implicit_remainder":
        # 70% of 48 wanted in rack 0; the remainder, 14.4, is the implicit
        # target of every other value of the table (stack.py, spread.go:258)
        return [stanza(racks, desired=[33.6, 14.4, 14.4, 14.4, 14.4])], None
    if name == "bucketless_nodes":
        lacking = racks.copy()
        lacking[rng.choice(n, 6, replace=False)] = -1
        return [stanza(lacking, even=True),
                stanza(dcs, desired=[30.0, 18.0], weight=0.5)], None
    if name == "seeded_counts":
        # counts from the job's live allocations, one in a bucket (5) that
        # no node of this cluster has: it still sets minc and maxc
        return [stanza(racks, counts=[4, 0, 2, 7, 1, 3], even=True),
                stanza(dcs, counts=[5, 1], desired=[24.0, 24.0],
                       weight=0.5)], None
    if name == "inactive_between_active":
        return [stanza(racks, even=True),
                stanza(np.arange(n) % 3, counts=[9, 0, 4], even=True),
                stanza(dcs, desired=[36.0, 12.0], weight=0.5)], np.array(
                    [True, False, True, False])
    if name == "places_nothing":
        return [stanza(racks, counts=[1, 0, 0, 2, 0], even=True)], None
    raise AssertionError(name)


SPREAD_CASES = ["even", "desired_implicit_remainder", "bucketless_nodes",
                "seeded_counts", "inactive_between_active", "places_nothing"]


def _spread_by_bucket_table(stanzas, active, counts, n=SPREAD_N_NODES):
    """float64 spread plane over the ``n`` real nodes, every boost computed
    over the BUCKET table and then looked up by each node's bucket."""
    total = np.zeros(n)
    for s, sp in enumerate(stanzas):
        if not active[s]:
            continue
        cnt = counts[s]
        if sp.even:
            present = cnt > 0
            if not present.any():
                table = np.zeros(SPREAD_BUCKETS)
            else:
                minc, maxc = cnt[present].min(), cnt[present].max()
                at_min = (-1.0 if minc == maxc
                          else 1.0 if minc == 0 else (maxc - minc) / minc)
                table = np.where(cnt != minc, (minc - cnt) / minc, at_min)
        else:
            des = sp.desired.astype(np.float64)
            safe = np.where(des > 0, des, 1.0)
            table = np.where(
                des > 0, ((des - (cnt + 1)) / safe) * sp.weight_frac, -1.0)
        bucket = sp.bucket_id[:n]
        total += np.where(bucket >= 0, table[np.clip(bucket, 0, None)], -1.0)
    return total


def _spread_reference(cluster, members, order):
    """Place ``order``'s steps (member, local index) one after the other
    over a shared capacity carry, as the wave program does: float64,
    first-best node, spreads by bucket table. ``members`` is a list of
    (ev, active, n_steps). It is ``_pick_reference`` under the identity
    permutation, with no pin and no penalty."""
    steps = 1 + max(local for _, local in order)
    rows = _pick_reference(
        cluster,
        [dict(ev=ev, active=active, n_steps=n_steps,
              penalty=np.full((steps, 1), -1), preferred=np.full(steps, -1))
         for ev, active, n_steps in members],
        order, [np.arange(cluster.n_pad)] * len(members))
    return [(node, score) for node, score, *_ in rows]


def _spread_problem(name, seed, n_members):
    """The parity cluster, the reference's view of its members and their
    KernelIn. Member 0 has the named case's stanzas, a second member
    another case's. The nodes hold every step's task, but in
    ``places_nothing``: there each member asks for 44 of its 48 steps
    and the nodes hold 40 a member, so four steps find no node and four
    are past ``n_steps``."""
    rng = np.random.default_rng(seed)
    n_steps, per_node = SPREAD_STEPS, 3
    if name == "places_nothing":
        n_steps, per_node = SPREAD_STEPS - 4, 2
    # three node sizes, so that binpack tells nodes apart
    room = 1000 * per_node * n_members + rng.choice([0, 300, 700],
                                                    SPREAD_N_NODES)
    cluster = make_cluster([(r, r) for r in room])
    members, kins = [], []
    for m in range(n_members):
        case = SPREAD_CASES[(SPREAD_CASES.index(name) + 2 * m)
                            % len(SPREAD_CASES)]
        stanzas, active = _spread_stanzas(case, rng)
        ev = make_eval(cluster, ask=simple_ask(cpu=1000, mem=1000),
                       spreads=stanzas, desired_count=SPREAD_STEPS)
        kin = build_kernel_in(cluster, ev, n_steps)
        if active is None:
            active = np.asarray(kin.spread_active)
        kins.append(kin._replace(spread_active=active))
        members.append((ev, active, n_steps))
    return cluster, members, kins


def _stack_kins(kins):
    """A wave's members with a leading member axis on every leaf."""
    from nomad_tpu.ops.kernel import KernelIn

    return KernelIn(*[np.stack([np.asarray(getattr(k, f)) for k in kins])
                      for f in KernelIn._fields])


def _assert_spread_parity(got_chosen, got_scores, want):
    for step, (node, score) in enumerate(want):
        assert got_chosen[step] == node, (
            f"step {step}: program chose {got_chosen[step]}, the bucket "
            f"table {node} (scores {got_scores[step]}, {score})")
        if node >= 0:
            assert got_scores[step] == pytest.approx(score, abs=1e-6), step


class TestSpreadNodeAxis:
    @pytest.mark.parametrize("name", SPREAD_CASES)
    def test_single_matches_bucket_table(self, name):
        cluster, members, kins = _spread_problem(name, seed=11, n_members=1)
        out = place_taskgroup_jit(kins[0], SPREAD_STEPS)
        want = _spread_reference(
            cluster, members, [(0, i) for i in range(SPREAD_STEPS)])
        assert sum(node < 0 for node, _ in want) == (
            8 if name == "places_nothing" else 0)
        _assert_spread_parity(np.asarray(out.chosen), np.asarray(out.scores),
                              want)

    @pytest.mark.parametrize("name", SPREAD_CASES)
    def test_joint_matches_bucket_table(self, name):
        """Two members over the same nodes, their steps interleaved: the
        capacity carry is shared, the two spread carries are per member."""
        import jax.numpy as jnp

        from nomad_tpu.ops.kernel import place_taskgroups_joint_jit

        cluster, members, kins = _spread_problem(name, seed=12, n_members=2)
        order = [(t % 2, t // 2) for t in range(2 * SPREAD_STEPS)]
        out = place_taskgroups_joint_jit(
            _stack_kins(kins), jnp.asarray([m for m, _ in order], jnp.int32),
            jnp.asarray([i for _, i in order], jnp.int32), len(order))
        want = _spread_reference(cluster, members, order)
        assert sum(node < 0 for node, _ in want) == (
            16 if name == "places_nothing" else 0)
        _assert_spread_parity(np.asarray(out.chosen), np.asarray(out.scores),
                              want)


def _node_and_bucket_values(jaxpr, n_pad, inside_scan=False):
    """(loop bodies met, offending values): every value produced or read
    inside a ``scan`` or ``while`` body of ``jaxpr`` (the joint program's
    step loop is a ``while``: its trip count is the wave's real steps),
    however deeply nested, whose shape has both an ``n_pad``-sized and a
    SPREAD_BUCKETS-sized axis."""
    scans, bad = 0, []
    for eqn in jaxpr.eqns:
        if inside_scan:
            for v in list(eqn.invars) + list(eqn.outvars):
                shape = getattr(v.aval, "shape", ())
                if n_pad in shape and SPREAD_BUCKETS in shape:
                    bad.append((eqn.primitive.name, shape))
        is_scan = eqn.primitive.name in ("scan", "while")
        scans += is_scan
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple)) else [param]):
                sub = getattr(sub, "jaxpr", sub)      # ClosedJaxpr or Jaxpr
                if hasattr(sub, "eqns"):
                    s, b = _node_and_bucket_values(
                        sub, n_pad, inside_scan or is_scan)
                    scans += s
                    bad += b
    return scans, bad


class TestNoNodeByBucketValueInAStep:
    """ISSUE 27: spread scoring works on the node axis and on the bucket
    axis separately; a placement step never builds or reads the
    [S, n_pad, SPREAD_BUCKETS] one-hot (33.5 MB a step at the benchmark
    cell's shape)."""

    N_PAD = 256            # not SPREAD_BUCKETS: the two axes stay apart

    def _kin(self):
        from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

        cluster = synthetic_cluster(self.N_PAD - 20, seed=5)
        assert cluster.n_pad == self.N_PAD != SPREAD_BUCKETS
        ev = synthetic_eval(cluster, with_spread=True, used_frac=0.3, seed=5)
        return build_kernel_in(cluster, ev, 8)

    def test_the_walker_sees_a_one_hot_in_a_scan(self):
        import jax
        import jax.numpy as jnp

        def old_way(bucket):
            def step(carry, _):
                onehot = jax.nn.one_hot(bucket, SPREAD_BUCKETS)
                return carry + onehot.sum(), None
            return jax.lax.scan(step, 0.0, jnp.arange(3))[0]

        scans, bad = _node_and_bucket_values(
            jax.make_jaxpr(old_way)(jnp.zeros(self.N_PAD, jnp.int32)).jaxpr,
            self.N_PAD)
        assert scans == 1 and bad

    @pytest.mark.parametrize("program", ["place_taskgroup",
                                         "place_taskgroups_joint"])
    def test_scan_body(self, program):
        import jax

        from nomad_tpu.ops import kernel as K
        from nomad_tpu.tensors.schema import MAX_SPREADS

        assert K.FULL_FEATURES.n_spreads == MAX_SPREADS
        kin = self._kin()
        if program == "place_taskgroup":
            jaxpr = jax.make_jaxpr(
                lambda k: K.place_taskgroup(k, 8, K.FULL_FEATURES))(kin)
        else:
            stacked = K.KernelIn(*[np.stack([np.asarray(x)] * 2) for x in kin])
            member = np.repeat(np.arange(2, dtype=np.int32), 8)
            local = np.tile(np.arange(8, dtype=np.int32), 2)
            jaxpr = jax.make_jaxpr(
                lambda k, m, j: K.place_taskgroups_joint(
                    k, m, j, 16, K.FULL_FEATURES))(stacked, member, local)
        scans, bad = _node_and_bucket_values(jaxpr.jaxpr, self.N_PAD)
        assert scans >= 1, "no scan found: the guard has nothing to walk"
        assert not bad, bad[:5]


# ---------------------------------------------------------------------------
# The seeded tie-break (ISSUE 30): the programs take it from a rank plane
# and two reductions; upstream states it as a walk over shuffled nodes
# ---------------------------------------------------------------------------

PICK_NEG = -1.0e30          # what a program writes for a masked-out node
PICK_TOPK = 8               # rows of score metadata a step returns
PICK_CASES = ["tied", "identity_permutation", "no_feasible_step",
              "preferred_pin", "step_penalty", "spread_1", "spread_4"]
PICK_PROGRAMS = ["single_full", "joint_shared_perm", "joint_member_perms"]


def _pick_reference(cluster, members, order, perms):
    """Place ``order``'s steps (member, local index) over a shared
    capacity carry in float64, imports nothing of the program. The
    tie-break is stated as upstream has it (shuffleNodes util.go:464, then
    the first best of the walk): ``perm[argmax(masked[perm])]`` over the
    padded node axis. ``members``: dicts of ``ev``, ``active``,
    ``n_steps``, ``penalty`` (i32[K, P] node ids, -1 none) and
    ``preferred`` (i32[K], -1 none). One row a step: chosen, score,
    found, top-k nodes, top-k scores.

    What a member may also state, each over the nodes and its own: where
    it starts (``used_cpu``, ``used_mem``: what its snapshot holds, under
    the wave's shared additions), its job's allocations there
    (``job_tg_count``, ``job_any_count``) with ``distinct_tg`` and
    ``distinct_job``, and its ports: ``dyn_ports`` asked of a node's free
    ones (the wave's additions are shared), ``reserved_ports`` with the
    nodes where they are taken (``port_conflict``; a placement takes them
    for this member's later steps)."""
    n, n_pad = cluster.n_real, cluster.n_pad
    cap_c = cluster.cap_cpu[:n].astype(np.float64)
    cap_m = cluster.cap_mem[:n].astype(np.float64)
    cap_d = cluster.cap_disk[:n].astype(np.float64)
    free_dyn = cluster.free_dyn[:n].astype(np.int64)

    def own(key, dtype=np.float64):
        return [np.array(mb.get(key, np.zeros(n)))[:n].astype(dtype)
                for mb in members]

    base_c, base_m = own("used_cpu"), own("used_mem")
    job_cnt, any_cnt = own("job_tg_count"), own("job_any_count")
    conflict = own("port_conflict", bool)
    # the wave's additions, seen by every member
    add_c, add_m, add_d = np.zeros(n), np.zeros(n), np.zeros(n)
    add_dyn = np.zeros(n, np.int64)
    counts = [[sp.counts.astype(np.float64).copy() for sp in mb["ev"].spreads]
              for mb in members]
    rows = []
    for m, local in order:
        mb = members[m]
        ev, ask = mb["ev"], mb["ev"].ask
        dyn, reserved = mb.get("dyn_ports", 0), mb.get("reserved_ports", False)
        masked = np.full(n_pad, PICK_NEG)
        feasible = np.zeros(n_pad, bool)
        if local < mb["n_steps"]:
            used_c, used_m = base_c[m] + add_c, base_m[m] + add_m
            ok = ((cap_c - used_c >= ask.cpu) & (cap_m - used_m >= ask.mem)
                  & (cap_d - add_d >= ask.disk))
            if dyn > 0:
                ok &= free_dyn - add_dyn >= dyn
            if reserved:
                ok &= ~conflict[m]
            if mb.get("distinct_job", False):
                ok &= any_cnt[m] == 0
            if mb.get("distinct_tg", False):
                ok &= job_cnt[m] == 0
            feasible[:n] = ok
            total = (10.0 ** (1 - (used_c + ask.cpu) / cap_c)
                     + 10.0 ** (1 - (used_m + ask.mem) / cap_m))
            penalized = np.isin(np.arange(n), mb["penalty"][local])
            planes = [np.clip(20.0 - total, 0.0, 18.0) / 18.0,
                      -(job_cnt[m] + 1) / max(ev.desired_count, 1),
                      np.full(n, -1.0)]
            on = [np.ones(n, bool), job_cnt[m] > 0, penalized]
            if ev.spreads:
                spread = _spread_by_bucket_table(
                    ev.spreads, mb["active"], counts[m], n)
                planes.append(spread)
                on.append(spread != 0.0)
            score = (sum(np.where(o, p, 0.0) for p, o in zip(planes, on))
                     / sum(o.astype(np.float64) for o in on))
            masked[:n] = np.where(feasible[:n], score, PICK_NEG)
        perm = perms[m]
        idx = int(perm[np.argmax(masked[perm])])
        pref = int(mb["preferred"][local])
        if pref >= 0 and feasible[pref]:
            idx = pref
        found = bool(masked[idx] > PICK_NEG / 2)
        top = np.argsort(-masked, kind="stable")[:PICK_TOPK]
        rows.append((idx if found else -1, masked[idx] if found else 0.0,
                     found, top, masked[top]))
        if found:
            add_c[idx] += ask.cpu
            add_m[idx] += ask.mem
            add_d[idx] += ask.disk
            add_dyn[idx] += dyn
            job_cnt[m][idx] += 1
            any_cnt[m][idx] += 1
            conflict[m][idx] |= bool(reserved)
            for s, sp in enumerate(ev.spreads):
                if mb["active"][s] and sp.bucket_id[idx] >= 0:
                    counts[m][s][sp.bucket_id[idx]] += 1
    return rows


def _pick_problem(case, program, seed):
    """Identical empty nodes (the benchmark cell's case: every node ties
    on a member's first step and the empty ones tie again whenever the
    node being filled is full), one member or four."""
    rng = np.random.default_rng(seed)
    n = SPREAD_N_NODES
    n_members = 1 if program == "single_full" else 4
    n_steps = 24 if n_members == 1 else 8
    # three tasks fit a node; one where the nodes are to run out, 20
    # places for 24 or 32 steps
    per_node = 1 if case == "no_feasible_step" else 3
    cluster = make_cluster([(1000 * per_node + 500,) * 2] * n)
    k_pad = pad_steps(n_steps)
    members, kins, perms = [], [], []
    shared_perm = rng.permutation(cluster.n_pad).astype(np.int32)
    for m in range(n_members):
        stanzas, active = [], np.zeros(4, bool)
        if case.startswith("spread"):
            stanzas, active = _spread_stanzas(
                "even" if case == "spread_1" else "inactive_between_active",
                rng)
        penalty = np.full((k_pad, 4), -1, np.int32)
        preferred = np.full(k_pad, -1, np.int32)
        if case == "step_penalty":
            penalty[:n_steps:2, 0] = rng.integers(0, n, len(penalty[:n_steps:2]))
            penalty[1, :] = rng.choice(n, 4, replace=False)
        if case == "preferred_pin":
            # the same node four steps running: it holds three
            preferred[2:6] = rng.integers(0, n)
            preferred[7] = rng.integers(0, n)
        if case == "identity_permutation":
            perm = np.arange(cluster.n_pad, dtype=np.int32)
        elif program == "joint_member_perms":
            perm = rng.permutation(cluster.n_pad).astype(np.int32)
        else:
            perm = shared_perm
        ev = make_eval(cluster, ask=simple_ask(cpu=1000, mem=1000),
                       spreads=stanzas, desired_count=n_steps)
        kin = build_kernel_in(cluster, ev, n_steps, step_penalty=penalty,
                              step_preferred=preferred, node_perm=perm)
        if active is None:
            active = np.asarray(kin.spread_active)
        kins.append(kin._replace(spread_active=active))
        members.append(dict(ev=ev, active=active, n_steps=n_steps,
                            penalty=penalty, preferred=preferred))
        perms.append(perm)
    return cluster, members, kins, perms, n_steps


def _run_pick_program(program, kins, order, features):
    import jax.numpy as jnp

    from nomad_tpu.ops.kernel import place_taskgroups_joint_jit

    if program == "single_full":
        return place_taskgroup_jit(kins[0], len(order), features)
    stacked = _stack_kins(kins)
    if program == "joint_shared_perm":
        # one permutation for the wave, shipped without a member axis
        assert all(np.array_equal(k.node_perm, kins[0].node_perm)
                   for k in kins)
        stacked = stacked._replace(node_perm=np.asarray(kins[0].node_perm))
    return place_taskgroups_joint_jit(
        stacked, jnp.asarray([m for m, _ in order], jnp.int32),
        jnp.asarray([i for _, i in order], jnp.int32), len(order), features)


class TestSeededTieBreak:
    @pytest.mark.parametrize("case", PICK_CASES)
    @pytest.mark.parametrize("program", PICK_PROGRAMS)
    def test_pick_is_the_first_best_of_the_shuffled_walk(self, program, case):
        from nomad_tpu.ops.kernel import FULL_FEATURES, NEG_INF, TOPK

        assert (NEG_INF, TOPK) == (PICK_NEG, PICK_TOPK)
        cluster, members, kins, perms, n_steps = _pick_problem(
            case, program, seed=30)
        k_pad = pad_steps(n_steps)
        n_m = len(members)
        # a wave's steps interleaved; a lone member's in order, the
        # padded steps past n_steps included
        order = [(t % n_m, t // n_m) for t in range(n_m * k_pad)]
        features = FULL_FEATURES._replace(
            with_shuffle=True,
            n_spreads={"spread_1": 1, "spread_4": 4}.get(case, 0))
        out = _run_pick_program(program, kins, order, features)
        want = _pick_reference(cluster, members, order, perms)

        chosen = np.array([r[0] for r in want])
        found = np.array([r[2] for r in want])
        real = np.array([i < n_steps for _, i in order])
        # the case holds what it is named for
        assert found[real].sum() >= (20 if case == "no_feasible_step"
                                     else real.sum())
        assert (~found[real]).sum() == {
            ("no_feasible_step", 1): 4, ("no_feasible_step", 4): 12,
        }.get((case, n_m), 0)
        if case not in ("identity_permutation", "preferred_pin"):
            first_best = [int(np.argmax(r[4] == r[4][0])) for r in want]
            assert any(r[0] >= 0 and r[0] != r[3][f]
                       for r, f in zip(want, first_best)), (
                "no step's tie went another way than to the first node")
        np.testing.assert_array_equal(np.asarray(out.chosen), chosen)
        np.testing.assert_array_equal(np.asarray(out.found), found)
        np.testing.assert_array_equal(
            np.asarray(out.topk_idx), np.stack([r[3] for r in want]))
        np.testing.assert_allclose(
            np.asarray(out.scores), [r[1] for r in want], rtol=0, atol=1e-6)
        top_want = np.stack([r[4] for r in want])
        top_got = np.asarray(out.topk_scores)
        masked_out = top_want < PICK_NEG / 2
        np.testing.assert_array_equal(top_got < PICK_NEG / 2, masked_out)
        np.testing.assert_allclose(
            np.where(masked_out, 0.0, top_got),
            np.where(masked_out, 0.0, top_want), rtol=0, atol=1e-6)

    @pytest.mark.parametrize("program", PICK_PROGRAMS)
    def test_identity_permutation_is_the_plain_argmax(self, program):
        """Bit for bit, every output: the rank plane of the identity is
        the node index, and the first best by rank is ``argmax``."""
        from nomad_tpu.ops.kernel import FULL_FEATURES

        _, members, kins, _, n_steps = _pick_problem(
            "identity_permutation", program, seed=31)
        n_m = len(members)
        order = [(t % n_m, t // n_m) for t in range(n_m * pad_steps(n_steps))]
        plain = FULL_FEATURES._replace(n_spreads=0)
        a = _run_pick_program(program, kins, order, plain)
        b = _run_pick_program(program, kins, order,
                              plain._replace(with_shuffle=True))
        for field in ("chosen", "found", "scores", "topk_idx", "topk_scores"):
            np.testing.assert_array_equal(
                np.asarray(getattr(a, field)), np.asarray(getattr(b, field)),
                err_msg=field)


def _gathers_in_loops(text):
    """(lines read, result shapes): every ``stablehlo.gather`` of a
    lowered module that a ``stablehlo.while`` runs, in the loop's own
    regions or in a private function they call, however deep."""
    import re

    funcs, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"\s*func\.func \w+ @([\w.]+)\(", line)
        if head:
            cur = funcs.setdefault(head.group(1), [])
        elif cur is not None:
            cur.append(line)
    looped = []
    for lines in funcs.values():
        depth = None                    # None: outside any while
        for line in lines:
            if depth is None:
                if "stablehlo.while" in line:
                    depth = 0
                continue
            looped.append(line)
            depth += line.count("{") - line.count("}")
            if depth == 0:              # the `do` region closed
                depth = None
    seen, at = set(), 0
    while at < len(looped):
        for name in re.findall(r"call @([\w.]+)", looped[at]):
            if name not in seen:
                seen.add(name)
                looped.extend(funcs[name])
        at += 1
    shapes = []
    for line in looped:
        if re.search(r"stablehlo\.(dynamic_)?gather", line):
            result = line.rsplit("->", 1)[1]
            dims = re.search(r"tensor<((?:\d+x)*)", result).group(1)
            shapes.append(tuple(int(d) for d in dims.split("x") if d))
    return len(looped), shapes


class TestNoNodeGatherInAStep:
    """ISSUE 30: a placement step breaks score ties by the rank plane,
    with two reductions; no step gathers a node-axis plane (16,384
    single elements a step were 116 of 172 us on the v5e). Read off the
    lowered text, which no backend has touched yet."""

    N_PAD = 256

    def _kin(self):
        from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

        cluster = synthetic_cluster(self.N_PAD - 20, seed=5)
        assert cluster.n_pad == self.N_PAD
        ev = synthetic_eval(cluster, with_spread=True, used_frac=0.3, seed=5)
        perm = np.random.default_rng(5).permutation(self.N_PAD)
        return build_kernel_in(cluster, ev, 8, node_perm=perm.astype(np.int32))

    def test_the_reader_sees_a_gather_in_a_scan(self):
        import jax
        import jax.numpy as jnp

        @jax.jit
        def shuffled(masked, perm):     # outlined: a call from the loop
            return perm[jnp.argmax(masked[perm])]

        def old_way(masked, perm):
            def step(carry, _):
                return carry + shuffled(masked + carry, perm), None
            return jax.lax.scan(step, 0, jnp.arange(3))[0]

        text = jax.jit(old_way).lower(
            jnp.zeros(self.N_PAD), jnp.arange(self.N_PAD)).as_text()
        lines, shapes = _gathers_in_loops(text)
        assert lines and (self.N_PAD,) in shapes, shapes

    @pytest.mark.parametrize("program", ["place_taskgroup",
                                         "place_taskgroups_joint"])
    def test_scan_body(self, program):
        import jax

        from nomad_tpu.ops import kernel as K

        features = K.FULL_FEATURES._replace(with_shuffle=True)
        kin = self._kin()
        if program == "place_taskgroup":
            text = jax.jit(K.place_taskgroup, static_argnums=(1, 2)).lower(
                kin, 8, features).as_text()
        else:
            stacked = K.KernelIn(*[np.stack([np.asarray(x)] * 2) for x in kin])
            member = np.repeat(np.arange(2, dtype=np.int32), 8)
            local = np.tile(np.arange(8, dtype=np.int32), 2)
            text = jax.jit(K.place_taskgroups_joint,
                           static_argnums=(3, 4)).lower(
                stacked, member, local, 16, features).as_text()
        lines, shapes = _gathers_in_loops(text)
        assert lines > 100, "no loop found: the guard has nothing to read"
        assert not [s for s in shapes if self.N_PAD in s], shapes
        # the rank plane is there, scattered once a launch
        assert f"tensor<{self.N_PAD}xi32>" in text and "stablehlo.scatter" in text


# ---------------------------------------------------------------------------
# A wave's real steps alone (ISSUE 37): the joint program's loop runs the
# steps of a member inside its n_steps and leaves every other row as an
# inert step writes it; padding the step axis changes no answer
# ---------------------------------------------------------------------------

#: (members, k): waves of one to four members in their wave bucket, k of
#: 1, 8 and 300 (the cell's); spread and top-k each on and off among
#: them; the shuffle, step penalties and preferred pins on in every case
REAL_STEP_CASES = [
    (1, 1, True, True), (1, 300, False, False), (2, 8, True, False),
    (2, 300, False, True), (3, 1, False, True), (3, 300, True, True),
    (4, 8, False, True), (4, 300, True, False),
]


def _real_step_wave(members, k, spread, seed=37):
    """``members`` members of ``k`` steps each, as the launcher stacks
    them: every leaf member-stacked, the wave bucket's spare slots the
    first member with no steps, each member's step planes ``k_pad``
    long with a penalty on every real step and a quarter of them pinned."""
    from nomad_tpu.ops.kernel import MAX_PENALTY_NODES, pad_steps_live
    from nomad_tpu.parallel.coalesce import pad_wave
    from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

    rng = np.random.default_rng([seed, members, k])
    cluster = synthetic_cluster(100, cpu=8000.0, mem=16384.0, seed=seed)
    k_pad = pad_steps_live(k)
    kins = []
    for m in range(members):
        ev = synthetic_eval(
            cluster, ask_cpu=float(rng.choice([100, 200, 300])),
            ask_mem=float(rng.choice([64, 128, 256])), with_spread=spread,
            used_frac=0.5, seed=seed + m)
        pen = np.full((k_pad, MAX_PENALTY_NODES), -1, np.int32)
        pen[:k, 0] = rng.integers(0, cluster.n_real, k)
        pref = np.full(k_pad, -1, np.int32)
        pinned = rng.choice(k, max(1, k // 4), replace=False)
        pref[pinned] = rng.integers(0, cluster.n_real, len(pinned))
        kins.append(build_kernel_in(
            cluster, ev, k, pen, pref,
            node_perm=rng.permutation(cluster.n_pad).astype(np.int32)))
    kins += [kins[0]._replace(n_steps=np.asarray(0, np.int32))] * (
        pad_wave(members) - members)
    return _stack_kins(kins), k_pad


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint32) if x.dtype == np.float32 else x


class TestRealStepsOnly:
    @pytest.mark.parametrize("members, k, spread, topk", REAL_STEP_CASES)
    def test_padding_changes_no_answer(self, members, k, spread, topk):
        """Bit for bit, the launcher's padded layout (each member's steps
        in a ``k_pad`` block, the step axis ``wave_step_pad`` long) gives
        the real rows, the final carry and the member metrics of the same
        wave laid out with no inert step at all; every padded row holds
        what an inert step writes."""
        import jax.numpy as jnp

        from nomad_tpu.ops.kernel import (
            FULL_FEATURES,
            FUSED_METRIC_FIELDS,
            NEG_INF,
            TOPK,
            place_taskgroups_joint_jit,
        )
        from nomad_tpu.parallel.coalesce import wave_step_pad

        stacked, k_pad = _real_step_wave(members, k, spread)
        features = FULL_FEATURES._replace(
            n_spreads=1 if spread else 0, with_topk=topk, with_shuffle=True,
            with_devices=False, with_ports=False, with_cores=False,
            with_network=False, with_distinct=False)
        t_pad = wave_step_pad(members, k_pad)
        total = members * k
        member = np.full(t_pad, -1, np.int32)
        local = np.zeros(t_pad, np.int32)
        member[:members * k_pad] = np.repeat(np.arange(members), k_pad)
        local[:members * k_pad] = np.tile(np.arange(k_pad), members)
        real = (member >= 0) & (local < k)
        assert real.sum() == total

        padded = place_taskgroups_joint_jit(
            stacked, jnp.asarray(member), jnp.asarray(local), t_pad, features)
        bare = place_taskgroups_joint_jit(
            stacked, jnp.asarray(np.repeat(np.arange(members), k)),
            jnp.asarray(np.tile(np.arange(k), members)), total, features)

        rows = ("chosen", "scores", "found", "topk_idx", "topk_scores")
        for field in rows:
            np.testing.assert_array_equal(
                _bits(getattr(padded, field))[real],
                _bits(getattr(bare, field)), err_msg=field)
        for field in FUSED_METRIC_FIELDS + ("a_cpu", "a_mem", "a_disk"):
            np.testing.assert_array_equal(
                _bits(getattr(padded, field)), _bits(getattr(bare, field)),
                err_msg=field)
        fill = dict(
            chosen=np.int32(-1), scores=_bits(np.float32(0.0)), found=False,
            topk_idx=np.arange(TOPK) if topk else np.zeros(TOPK, np.int32),
            topk_scores=_bits(np.float32(NEG_INF)))
        for field in rows:
            got = _bits(getattr(padded, field))[~real]
            np.testing.assert_array_equal(
                got, np.broadcast_to(fill[field], got.shape), err_msg=field)
        # every real step places (the cluster has room for the wave): the
        # penalties and pins steer, they do not stall, and no real step
        # was left out of either loop
        assert np.asarray(bare.found).all()
