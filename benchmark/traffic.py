"""The traffic generator's data side: a workload file's parameters and
a seed in, the jobs of a run out, and the harness's record of each.

The jobs are a deck built from the configuration's ``job_shapes``
(restricted to the workload's ``kinds``), the same multiset for every
seed, shuffled by the seed. How they are offered is the workload's
``loop``, a module of ``loops/`` found by that name.

A job is done when the API says so: the event stream
(``/v1/event/stream``, topic Evaluation) delivers an evaluation of the
job that is complete, failed no task group, left nothing queued and
spawned no blocked evaluation.
"""

from __future__ import annotations

import numpy as np

from .generators import jobs as jobs_mod


class JobRecord:
    """The harness's own record of one job it sent."""

    __slots__ = ("seq", "id", "plain", "body", "client", "t_send", "t_ack",
                 "t_done", "acked", "refused", "stopped")

    def __init__(self, seq, plain, body):
        self.seq = seq
        self.id = plain["id"]
        self.plain = plain
        self.body = body
        self.client = -1
        self.t_send = self.t_ack = self.t_done = None
        self.acked = False
        self.refused = None
        self.stopped = False


def build_deck(config: dict, workload: dict, seed: int, tag: str) -> list:
    """The jobs of a run, in the order the clients will send them."""
    from nomad_tpu.api.codec import encode

    shapes = [s for s in config["job_shapes"]
              if not workload.get("kinds") or s["kind"] in workload["kinds"]]
    rng = np.random.default_rng([seed, 1])
    cards = jobs_mod.deck(shapes, workload["deck"])
    records = []
    for seq, ci in enumerate(rng.permutation(len(cards)).tolist()):
        si, count = cards[ci]
        job, plain = jobs_mod.make_job(
            shapes[si], f"{tag}-{seq}-{shapes[si]['kind']}", count, rng,
            config["cluster"])
        records.append(JobRecord(seq, plain, encode(job)))
    return records


def evaluation_says_done(ev: dict) -> bool:
    """An evaluation's update, as the event stream carries it: complete,
    nothing failed, nothing queued, nothing blocked, and not the
    evaluation of a deregister."""
    return (ev.get("Status") == "complete"
            and ev.get("TriggeredBy") != "job-deregister"
            and not ev.get("FailedTGAllocs")
            and not ev.get("BlockedEval")
            and not any((ev.get("QueuedAllocations") or {}).values()))
