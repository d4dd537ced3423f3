"""The spread's bucket plane from ``ClusterTensors.spread_codes`` (ISSUE
32): ``XLAGenericStack._build_spreads`` against the walk over every
node that it replaced, kept here as a plain function. Every field of
every ``SpreadTensor`` is held bit for bit, over seeds.
"""

from typing import Dict, List, Optional

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.stack import XLAGenericStack
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import consts
from nomad_tpu.structs.constraints import (
    Spread,
    SpreadTarget,
    resolve_target,
)
from nomad_tpu.structs.eval_plan import Plan
from nomad_tpu.tensors.schema import (
    SPREAD_BUCKETS,
    ClusterTensors,
    SpreadTensor,
)


def walk_build_spreads(stack, tg, job_allocs) -> List[SpreadTensor]:
    """``_build_spreads`` as it stood before ISSUE 32: one walk over the
    cluster's nodes per stanza per call."""
    c = stack.cluster
    job = stack.job
    combined = list(tg.spreads) + list(job.spreads)
    if not combined:
        return []
    sum_weights = sum(abs(s.weight) for s in combined)
    out = []
    plan_allocs = [
        a
        for allocs in stack.ctx.plan.node_allocation.values()
        for a in allocs
        if a.job_id == job.id and a.task_group == tg.name
    ]
    live_allocs = [
        a
        for a in job_allocs
        if not a.terminal_status() and a.task_group == tg.name
    ] + plan_allocs
    node_of = {nid: i for i, nid in enumerate(c.node_ids)}
    for spread in combined:
        values: Dict[str, int] = {}
        for t in spread.spread_target:
            if t.value != "*":
                values.setdefault(t.value, len(values))
        bucket_id = np.full(c.n_pad, -1, np.int32)
        node_vals: List[Optional[str]] = [None] * c.n_real
        for i in range(c.n_real):
            node = stack.ctx.state.node_by_id(c.node_ids[i])
            if node is None:
                continue
            val, ok = resolve_target(spread.attribute, node)
            if not ok:
                continue
            node_vals[i] = val
            if val not in values:
                if len(values) >= SPREAD_BUCKETS:
                    continue
                values[val] = len(values)
            bucket_id[i] = values[val]
        counts = np.zeros(SPREAD_BUCKETS, np.float32)
        for a in live_allocs:
            row = node_of.get(a.node_id)
            if row is None or node_vals[row] is None:
                continue
            b = values.get(node_vals[row])
            if b is not None:
                counts[b] += 1
        desired = np.full(SPREAD_BUCKETS, -1.0, np.float32)
        even = not spread.spread_target
        if not even:
            total_count = float(tg.count)
            sum_desired = 0.0
            implicit_pct = None
            for t in spread.spread_target:
                dc = (float(t.percent) / 100.0) * total_count
                if t.value == "*":
                    implicit_pct = dc
                    continue
                desired[values[t.value]] = dc
                sum_desired += dc
            remainder = total_count - sum_desired
            if implicit_pct is None and 0 < sum_desired < total_count:
                implicit_pct = remainder
            if implicit_pct is not None:
                for v, b in values.items():
                    if desired[b] < 0:
                        desired[b] = implicit_pct
        out.append(
            SpreadTensor(
                bucket_id=bucket_id,
                counts=counts,
                desired=desired,
                weight_frac=(float(spread.weight) / float(sum_weights)
                             if sum_weights else 0.0),
                even=even,
            )
        )
    return out


def assert_spreads_equal(got: List[SpreadTensor], want: List[SpreadTensor]):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        for f in ("bucket_id", "counts", "desired"):
            ga, wa = getattr(g, f), getattr(w, f)
            assert ga.dtype == wa.dtype, f
            assert ga.shape == wa.shape, f
            assert ga.tobytes() == wa.tobytes(), f
        assert g.weight_frac == w.weight_frac
        assert type(g.weight_frac) is type(w.weight_frac)
        assert g.even is w.even


class Scene:
    """A store of nodes, a job with spreads, allocations of it in the
    store and in the plan: what one call of ``_build_spreads`` reads."""

    def __init__(self, rng, n_nodes: int, racks: int = 7):
        self.rng = rng
        self.store = StateStore()
        self.nodes = []
        for i in range(n_nodes):
            node = mock.node(
                datacenter=f"dc{int(rng.integers(1, 4))}",
                node_class=("", "big", "small")[int(rng.integers(0, 3))])
            node.meta = {"rack": f"r{int(rng.integers(0, racks))}"}
            node.attributes["zone"] = f"z{int(rng.integers(0, 5))}"
            self.nodes.append(node)
        self.job = mock.job()
        self.tg = self.job.task_groups[0]
        self.tg.count = 20
        self.plan = Plan()

    def commit_nodes(self):
        for node in self.nodes:
            self.store.upsert_node(node)

    def add_allocs(self, live: int, terminal: int, planned: int,
                   other_group: int = 0, unknown_node: int = 0):
        rng, job, tg = self.rng, self.job, self.tg

        def make(**kw):
            node = self.nodes[int(rng.integers(0, len(self.nodes)))]
            kw.setdefault("node_id", node.id)
            kw.setdefault("task_group", tg.name)
            return mock.alloc(job_id=job.id, job=job, **kw)

        stored = [make() for _ in range(live)]
        stored += [make(desired_status=consts.ALLOC_DESIRED_STOP)
                   for _ in range(terminal // 2)]
        stored += [make(client_status=consts.ALLOC_CLIENT_COMPLETE)
                   for _ in range(terminal - terminal // 2)]
        stored += [make(task_group="other") for _ in range(other_group)]
        stored += [make(node_id="no-such-node") for _ in range(unknown_node)]
        if stored:
            self.store.upsert_allocs(stored)
        for _ in range(planned):
            a = make()
            self.plan.node_allocation.setdefault(a.node_id, []).append(a)
        # another job's placements in the plan are not this group's
        for _ in range(planned // 2):
            a = mock.alloc(node_id=self.nodes[0].id)
            self.plan.node_allocation.setdefault(a.node_id, []).append(a)

    def stack(self):
        self.store.upsert_job(self.job)
        snap = self.store.snapshot()
        cluster = ClusterTensors.build(snap.nodes())
        st = XLAGenericStack(False, EvalContext(snap, self.plan), cluster)
        st.set_job(self.job)
        job_allocs = snap.allocs_by_job(self.job.namespace, self.job.id)
        return st, job_allocs


def _even(rng):
    s = Scene(rng, 90)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=100)]
    return s


def _targets_no_star(rng):
    # 30 + 20 of 100: the implicit remainder goes to every other value
    s = Scene(rng, 90)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=70, spread_target=[
        SpreadTarget("r3", 30), SpreadTarget("r1", 20)])]
    return s


def _targets_star(rng):
    s = Scene(rng, 90)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=70, spread_target=[
        SpreadTarget("r5", 40), SpreadTarget("*", 10),
        SpreadTarget("r-nobody-has", 25)])]
    return s


def _targets_sum_to_all(rng):
    # nothing left over: no implicit target, unnamed values stay at -1
    s = Scene(rng, 90)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=50, spread_target=[
        SpreadTarget("r0", 60), SpreadTarget("r2", 40)])]
    return s


def _job_and_group(rng):
    s = Scene(rng, 120)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=30),
                    Spread(attribute="${node.class}", weight=-20)]
    s.job.spreads = [Spread(attribute="${node.datacenter}", weight=50,
                            spread_target=[SpreadTarget("dc1", 50),
                                           SpreadTarget("dc2", 25)]),
                     Spread(attribute="${meta.rack}", weight=10,
                            spread_target=[SpreadTarget("r6", 80)])]
    return s


def _nodes_lacking(rng):
    s = Scene(rng, 100)
    for node in s.nodes:
        roll = rng.random()
        if roll < 0.3:
            node.meta = {}
        elif roll < 0.4:
            node.attributes.pop("zone")
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=60),
                    Spread(attribute="${attr.zone}", weight=40)]
    s.add_allocs(live=15, terminal=0, planned=0)
    return s


def _datacenter(rng):
    s = Scene(rng, 70)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${node.datacenter}", weight=100,
                           spread_target=[SpreadTarget("dc2", 70)])]
    s.add_allocs(live=9, terminal=2, planned=3)
    return s


def _attr(rng):
    s = Scene(rng, 70)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${attr.zone}", weight=100),
                    Spread(attribute="${attr.cpu.numcores}", weight=5),
                    Spread(attribute="${attr.no.such}", weight=5)]
    s.add_allocs(live=9, terminal=2, planned=3)
    return s


def _unique_id(rng):
    # one value a node: the table overflows at SPREAD_BUCKETS
    s = Scene(rng, SPREAD_BUCKETS + 40)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${node.unique.id}", weight=100),
                    Spread(attribute="${node.unique.name}", weight=50)]
    s.add_allocs(live=60, terminal=5, planned=20)
    return s


def _overflow_late_targets(rng):
    # more distinct values than buckets, and targets that name values
    # first seen after the table is full
    n = SPREAD_BUCKETS + 70
    s = Scene(rng, n)
    for i, node in enumerate(s.nodes):
        node.meta = {"rack": f"r{i % (SPREAD_BUCKETS + 30)}"}
    s.commit_nodes()
    late = [f"r{SPREAD_BUCKETS + int(k)}" for k in rng.integers(0, 30, 3)]
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=100, spread_target=[
        SpreadTarget(late[0], 20), SpreadTarget("r2", 10),
        SpreadTarget(late[1], 5), SpreadTarget(late[2], 5)])]
    s.add_allocs(live=80, terminal=10, planned=30)
    return s


def _live_terminal_planned(rng):
    s = Scene(rng, 90)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="${meta.rack}", weight=100)]
    s.job.spreads = [Spread(attribute="${meta.rack}", weight=40, spread_target=[
        SpreadTarget("r4", 50), SpreadTarget("*", 50)])]
    s.add_allocs(live=25, terminal=12, planned=14, other_group=6,
                 unknown_node=3)
    return s


def _literal_and_zero_weight(rng):
    # a literal target resolves to itself on every node; weights of 0
    # take the guarded quotient
    s = Scene(rng, 66)
    s.commit_nodes()
    s.tg.spreads = [Spread(attribute="rack", weight=0),
                    Spread(attribute="${meta.rack}", weight=0)]
    s.add_allocs(live=5, terminal=1, planned=2)
    return s


CASES = {
    "even": _even,
    "targets_implicit_remainder": _targets_no_star,
    "targets_star": _targets_star,
    "targets_sum_to_all": _targets_sum_to_all,
    "job_and_group": _job_and_group,
    "nodes_lacking_attribute": _nodes_lacking,
    "datacenter": _datacenter,
    "attr": _attr,
    "unique_id_overflow": _unique_id,
    "overflow_late_targets": _overflow_late_targets,
    "live_terminal_planned": _live_terminal_planned,
    "literal_and_zero_weight": _literal_and_zero_weight,
}


@pytest.mark.parametrize("seed", range(5))
@pytest.mark.parametrize("case", sorted(CASES))
def test_build_spreads_is_the_walk(case, seed):
    scene = CASES[case](np.random.default_rng([seed, len(case)]))
    st, job_allocs = scene.stack()
    want = walk_build_spreads(st, scene.tg, job_allocs)
    assert want, "the case must have a spread"
    # twice: the call that builds the codes and the call that finds them
    for how in ("built", "hit"):
        got, got_how = st._build_spreads(scene.tg, job_allocs)
        assert got_how == how
        assert_spreads_equal(got, want)
    # and bucket planes of two calls do not alias one another
    again, _ = st._build_spreads(scene.tg, job_allocs)
    for a, b in zip(got, again):
        assert a.bucket_id is not b.bucket_id
        assert a.bucket_id.flags.writeable


def test_the_cases_cover_what_they_name():
    """The parametrised cases above are only worth their names if the
    walk really meets what each says: overflow, late targets, counts."""
    rng = np.random.default_rng(7)
    scene = _overflow_late_targets(rng)
    st, job_allocs = scene.stack()
    (sp,) = walk_build_spreads(st, scene.tg, job_allocs)
    assert int(sp.bucket_id.max()) == SPREAD_BUCKETS - 1
    assert (sp.bucket_id[:st.cluster.n_real] == -1).any(), "overflow"
    # the first target is late-seen and still holds bucket 0
    assert (sp.bucket_id[:st.cluster.n_real] == 0).any()
    assert sp.counts.sum() > 0

    scene = _live_terminal_planned(np.random.default_rng(7))
    st, job_allocs = scene.stack()
    even, targeted = walk_build_spreads(st, scene.tg, job_allocs)
    assert even.even and not targeted.even
    assert even.counts.sum() == 25 + 14     # live and in-plan, this group
    assert len(job_allocs) == 25 + 12 + 6 + 3

    scene = _nodes_lacking(np.random.default_rng(7))
    st, job_allocs = scene.stack()
    rack, zone = walk_build_spreads(st, scene.tg, job_allocs)
    n = st.cluster.n_real
    assert (rack.bucket_id[:n] == -1).any()
    assert (zone.bucket_id[:n] == -1).any()
    assert (rack.bucket_id[n:] == -1).all()


def test_no_spread_is_none():
    scene = Scene(np.random.default_rng(0), 10)
    scene.commit_nodes()
    st, job_allocs = scene.stack()
    assert st._build_spreads(scene.tg, job_allocs) == ([], "none")
    assert st.cluster._spread_codes == {}
