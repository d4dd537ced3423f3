"""A stopped allocation is a shallow copy (structs.go
Plan.AppendStoppedAlloc: ``*newAlloc = *alloc``).

``Plan.append_stopped_alloc`` shares the source's nested objects
(resources, metrics, task states) instead of deep-copying them. That is
sound only while no writer mutates a stored allocation in place, so
these tests take a purge of a 300-allocation job through the plan
applier, the FSM and the store twice from the same inputs, once as the
plan does it now and once with the deep copy it replaced, and
compare everything a reader can see.
"""

from __future__ import annotations

import copy
import json
import os

import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler.generic import stop_stats
from nomad_tpu.scheduler.scheduler import new_scheduler
from nomad_tpu.server import stream
from nomad_tpu.server.fsm import (
    ALLOC_CLIENT_UPDATE,
    JOB_DEREGISTER,
    JOB_REGISTER,
    NomadFSM,
)
from nomad_tpu.server.plan_apply import Planner
from nomad_tpu.server.plan_queue import PlanQueue
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import consts
from nomad_tpu.structs.alloc import (
    AllocMetric,
    Allocation,
    RescheduleEvent,
    RescheduleTracker,
    TaskEvent,
    TaskState,
)
from nomad_tpu.structs.eval_plan import Plan

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

N_NODES = 60
N_ALLOCS = 300
JOB_ID = "purge-me"

#: what ``append_stopped_alloc`` must leave shared with its source
NESTED = ("allocated_resources", "metrics", "task_states",
          "desired_transition", "reschedule_tracker",
          "preempted_allocations")


def _deep_append_stopped_alloc(self, alloc, desired_desc,
                               client_status="", follow_up_eval_id=""):
    """The deep copy the shallow copy replaced: the reference
    the shared objects are held to."""
    new = alloc.copy_skip_job()
    new.desired_status = consts.ALLOC_DESIRED_STOP
    new.desired_description = desired_desc
    if client_status:
        new.client_status = client_status
    if follow_up_eval_id:
        new.follow_up_eval_id = follow_up_eval_id
    self.node_update.setdefault(alloc.node_id, []).append(new)


def _rich_alloc(i: int, node_id: str, job) -> Allocation:
    """A placed allocation with every nested object the stop shares."""
    a = mock.alloc(id=f"alloc-{i:04d}", eval_id="eval-place",
                   node_id=node_id, job_id=job.id, job=job,
                   name=f"{job.id}.web[{i}]")
    a.metrics = AllocMetric(
        nodes_evaluated=N_NODES, nodes_available={"dc1": N_NODES},
        score_meta=[(node_id, {"binpack": 0.5 + i / 1e4}, 0.5)],
        allocation_time_ns=1000 + i)
    a.task_states = {"web": TaskState(
        state="running", started_at_ns=10 + i,
        events=[TaskEvent(type="Started")])}
    a.reschedule_tracker = RescheduleTracker(events=[RescheduleEvent(
        reschedule_time_ns=5, prev_alloc_id="old", prev_node_id=node_id)])
    a.preempted_allocations = [f"gone-{i}"]
    return a


def _template():
    """One set of inputs, copied whole into each universe: nodes, a
    job of 300, its allocations and the purge evaluation."""
    nodes = [mock.node(id=f"node-{i:03d}") for i in range(N_NODES)]
    job = mock.job(id=JOB_ID)
    job.task_groups[0].count = N_ALLOCS
    allocs = [_rich_alloc(i, nodes[i % N_NODES].id, job)
              for i in range(N_ALLOCS)]
    ev = mock.eval(id="eval-purge", job_id=JOB_ID,
                   triggered_by=consts.EVAL_TRIGGER_JOB_DEREGISTER)
    return {"nodes": nodes, "job": job, "allocs": allocs, "eval": ev}


class _FSMPlanner:
    """The scheduler's planner, committing through the plan applier
    and the FSM as a server does."""

    def __init__(self, store, fsm):
        self.applier = Planner(store, PlanQueue(), pool_workers=1,
                               raft_apply=fsm.apply)
        self.plans = []
        self.evals = []

    def submit_plan(self, plan):
        plan.run_deferred()
        self.plans.append(plan)
        return self.applier.apply_one(plan), None

    def update_eval(self, evaluation):
        self.evals.append(evaluation)

    def create_eval(self, evaluation):
        self.evals.append(evaluation)

    def reblock_eval(self, evaluation):
        self.evals.append(evaluation)

    def serve_rs_meet_minimum_version(self):
        return True


def _universe(template):
    """Place the 300, purge the job, schedule the purge: each step
    through the FSM. Returns the store, the FSM, the event
    subscription, the snapshot before the stop, and the planner."""
    t = copy.deepcopy(template)
    store = StateStore()
    broker = stream.EventBroker(buffer_size=10_000)
    fsm = NomadFSM(store, event_broker=broker)
    sub = broker.subscribe()
    for n in t["nodes"]:
        store.upsert_node(n)
    fsm.apply(JOB_REGISTER, {"job": t["job"]})
    planner = _FSMPlanner(store, fsm)
    place = Plan(eval_id="eval-place", priority=50, job=t["job"])
    for a in t["allocs"]:
        place.append_alloc(a)
    assert planner.applier.apply_one(place).full_commit(place)[0]
    fsm.apply(JOB_DEREGISTER, {"namespace": "default", "job_id": JOB_ID,
                               "purge": True, "evals": [t["eval"]]})
    before = store.snapshot()
    new_scheduler("service", store.snapshot(), planner).process(t["eval"])
    return store, fsm, sub, before, planner


def _rows(store):
    return sorted(store.snapshot().allocs_iter(), key=lambda a: a.id)


def _usage(store):
    u = store.snapshot().usage
    planes = tuple(sorted(
        (nid, float(u.used_cpu[row]), float(u.used_mem[row]),
         float(u.used_disk[row]), int(u.used_special[row]),
         int(u.used_devices[row]), u.port_masks.get(row, 0))
        for nid, row in u.rows.items()))
    return planes, tuple(u.row_events), u.row_events_floor


def _events(sub):
    out = []
    while True:
        got = sub.next_events(timeout=0.05, max_events=1000)
        if not got:
            return out
        out.extend((e.topic, e.type, e.key, e.index, e.payload)
                   for e in got)


@pytest.fixture(scope="module")
def template():
    return _template()


@pytest.fixture(scope="module")
def universes(template):
    """The same purge twice: as the plan does it now, and with the
    deep copy in its place."""
    stop_stats.reset()
    shallow = _universe(template)
    stopped = stop_stats.snapshot()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(Plan, "append_stopped_alloc", _deep_append_stopped_alloc)
        deep = _universe(template)
    return shallow, deep, stopped


class TestAppendStoppedAlloc:
    @pytest.mark.parametrize("client_status,follow_up", [
        ("", ""),
        (consts.ALLOC_CLIENT_LOST, ""),
        ("", "eval-follow"),
        (consts.ALLOC_CLIENT_LOST, "eval-follow"),
    ], ids=["plain", "lost", "follow-up", "lost-follow-up"])
    def test_stop_fields_on_the_copy_alone(self, client_status, follow_up):
        job = mock.job(id=JOB_ID)
        src = _rich_alloc(7, "node-007", job)
        src.fit_meta()
        before = {k: (v, copy.deepcopy(v)) for k, v in vars(src).items()}
        plan = Plan()
        plan.append_stopped_alloc(src, "no longer needed", client_status,
                                  follow_up)
        (new,) = plan.node_update["node-007"]
        # the source: every top-level field the same object and value
        assert set(vars(src)) == set(before)
        for k, (obj, value) in before.items():
            assert vars(src)[k] is obj, k
            assert vars(src)[k] == value, k
        # the copy: the stop fields set, every other field the source's
        assert new is not src
        assert new.desired_status == consts.ALLOC_DESIRED_STOP
        assert new.desired_description == "no longer needed"
        assert new.client_status == (client_status or src.client_status)
        assert new.follow_up_eval_id == follow_up
        changed = {"desired_status", "desired_description",
                   "client_status", "follow_up_eval_id"}
        for f in Allocation.__dataclass_fields__:
            if f not in changed:
                assert getattr(new, f) is getattr(src, f), f
        assert new.job is job
        for f in NESTED:
            assert getattr(new, f) is getattr(src, f), f
        assert new._fit_meta_cache is src._fit_meta_cache

    def test_fit_meta_of_the_copy_is_the_sources(self):
        src = _rich_alloc(3, "node-003", mock.job(id=JOB_ID))
        src.fit_meta()
        plan = Plan()
        plan.append_stopped_alloc(src, "stop")
        (new,) = plan.node_update["node-003"]
        assert new.fit_meta() is src.fit_meta()
        assert new.port_meta() == src.port_meta()
        assert new.index() == src.index() == 3


class TestPurgeThroughFSM:
    def test_every_allocation_stopped(self, universes):
        (store, _, _, _, planner), _, stopped = universes
        rows = _rows(store)
        assert len(rows) == N_ALLOCS
        assert all(a.desired_status == consts.ALLOC_DESIRED_STOP
                   for a in rows)
        assert sum(len(v) for p in planner.plans
                   for v in p.node_update.values()) == N_ALLOCS
        # the counter the benchmark's ``stop_append_us`` reads
        assert stopped["allocs"] == N_ALLOCS
        assert stopped["seconds"] > 0

    def test_rows_match_the_deep_copy(self, universes):
        (store_s, *_), (store_d, *_), _ = universes
        rows_s, rows_d = _rows(store_s), _rows(store_d)
        assert [a.id for a in rows_s] == [a.id for a in rows_d]
        for a, b in zip(rows_s, rows_d):
            assert a == b, a.id
            for f in Allocation.__dataclass_fields__:
                assert getattr(a, f) == getattr(b, f), (a.id, f)

    def test_usage_planes_match_the_deep_copy(self, universes):
        (store_s, *_), (store_d, *_), _ = universes
        planes, row_events, floor = _usage(store_s)
        assert (planes, row_events, floor) == _usage(store_d)
        assert row_events
        # the stop freed every node
        assert all(cpu == 0 and mem == 0 for _, cpu, mem, *_ in planes)

    def test_stream_events_match_the_deep_copy(self, universes):
        (_, _, sub_s, _, _), (_, _, sub_d, _, _), _ = universes
        ev_s, ev_d = _events(sub_s), _events(sub_d)
        assert ev_s == ev_d
        kinds = {(topic, etype) for topic, etype, *_ in ev_s}
        assert (stream.TOPIC_JOB, "JobDeregistered") in kinds
        assert (stream.TOPIC_ALLOC, "PlanResult") in kinds

    def test_stopped_rows_share_the_pre_stop_objects(self, universes):
        """The mechanism engaged: the shallow side's stored stop holds
        the pre-stop generation's nested objects; the deep side's
        holds copies of them."""
        (store_s, _, _, before_s, _), (store_d, _, _, before_d, _), _ = \
            universes
        for store, before, shared in ((store_s, before_s, True),
                                      (store_d, before_d, False)):
            for a in _rows(store):
                pre = before.alloc_by_id(a.id)
                assert pre.desired_status == consts.ALLOC_DESIRED_RUN
                for f in NESTED:
                    assert (getattr(a, f) is getattr(pre, f)) is shared, f


def test_client_update_leaves_the_pre_stop_generation(template):
    """A client update after the stop replaces the row; the stored
    generations before it, which share their nested objects, read as
    they were."""
    store, fsm, _, before, _ = _universe(template)
    after_stop = store.snapshot()
    ids = [a.id for a in _rows(store)][:5]
    saved = {aid: {f: copy.deepcopy(getattr(before.alloc_by_id(aid), f))
                   for f in NESTED}
             for aid in ids}
    update = [Allocation(
        id=aid, client_status=consts.ALLOC_CLIENT_COMPLETE,
        client_description="done",
        task_states={"web": TaskState(state="dead", finished_at_ns=99)})
        for aid in ids]
    fsm.apply(ALLOC_CLIENT_UPDATE, {"allocs": update})
    latest = store.snapshot()
    for aid in ids:
        row = latest.alloc_by_id(aid)
        assert row.client_status == consts.ALLOC_CLIENT_COMPLETE
        assert row.task_states["web"].state == "dead"
        pre = before.alloc_by_id(aid)
        stopped = after_stop.alloc_by_id(aid)
        assert pre.client_status == consts.ALLOC_CLIENT_PENDING
        assert stopped.client_status == consts.ALLOC_CLIENT_PENDING
        assert stopped.desired_status == consts.ALLOC_DESIRED_STOP
        for f in NESTED:
            assert getattr(pre, f) == saved[aid][f], (aid, f)
            assert getattr(stopped, f) == saved[aid][f], (aid, f)
        assert pre.task_states["web"].state == "running"
        assert row.task_states is not pre.task_states


def test_stop_append_us_file_reads_the_counters():
    """``stop_append_us`` as ``BENCHMARK.json`` lists it: microseconds
    per stopped allocation; nothing on a parent that lacks the counter
    (``benchmark/tracing._read_counters``)."""
    from benchmark.readers import counter
    from benchmark.tracing import resolve

    with open(os.path.join(ROOT, "benchmark", "metrics",
                           "stop_append_us.json")) as f:
        metric = json.load(f)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        listed = {m["name"]: m for m in json.load(f)["per_layer"]}
    for key in ("name", "unit", "better", "source", "layer", "moves",
                "workloads"):
        assert metric[key] == listed["stop_append_us"][key], key
    seconds, allocs = metric["counters"]
    stop_stats.reset()
    before = {p: float(resolve(p, {})) for p in metric["counters"]}
    stop_stats.observe(300, 0.003)
    stop_stats.observe(300, 0.003)
    ctx = {"counters": {p: (before[p], float(resolve(p, {})))
                        for p in metric["counters"]}}
    stop_stats.reset()
    assert counter.read(metric, ctx) == pytest.approx(10.0)
    # no purge in the window
    assert counter.read(metric, {"counters": {
        seconds: (0.5, 0.5), allocs: (300.0, 300.0)}}) is None
    assert counter.read(metric, {"counters": {
        seconds: (None, None), allocs: (None, None)}}) is None
