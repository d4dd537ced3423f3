"""``"loop": "closed"``: ``outstanding`` logical clients, each of
which registers its next job the moment its last one is done (and,
with ``stop_when_done``, has deregistered it with purge).

One thread reads the event stream and ``senders`` threads register:
load from one process with few threads.

A module of ``loops/`` exposes ``Loop(api, deck, workload)`` with
``start_listener``, ``send_one_and_wait``, ``start_clients``,
``next_done``, ``close``, ``in_flight``, ``quit``, and the attributes
``jobs`` (job id -> JobRecord, once sent), ``errors`` and
``done_times``: what ``run.py`` drives.
"""

from __future__ import annotations

import queue
import threading
import time

from ..traffic import JobRecord, evaluation_says_done

#: the client of the one job that is sent alone; it sends no second
LONE = -1


class Loop:
    """``outstanding`` clients over one API address."""

    def __init__(self, api, deck: list, workload: dict):
        self.api = api
        self.deck = deck
        self.workload = workload
        self.jobs: dict = {}            # job id -> JobRecord, once sent
        self.errors: list = []
        self._lock = threading.Lock()
        self._next = 0
        self._free: queue.Queue = queue.Queue()
        self._closing = threading.Event()
        self._quit = threading.Event()
        self._threads: list = []
        self._last_of: dict = {}        # client -> its newest JobRecord
        self.stream_ready = threading.Event()
        self.done_times: list = []      # in order, by the one listener

    # -- the event stream ------------------------------------------------

    def _listen(self) -> None:
        try:
            # the stream holds for 600 s and is kept alive every 5 s
            frames = self.api.stream(
                "/v1/event/stream", [("topic", "Evaluation")], timeout=30.0)
            self.stream_ready.set()
            for batch in frames:
                now = time.monotonic()
                for event in batch.get("Events", ()):
                    ev = event.get("Payload") or {}
                    rec = self.jobs.get(ev.get("JobID"))
                    if rec is None or rec.t_done is not None:
                        continue
                    if evaluation_says_done(ev):
                        rec.t_done = now
                        self.done_times.append(now)
                        if rec.client != LONE:
                            self._free.put(rec.client)
                if self._quit.is_set():
                    return
        except Exception as e:                      # noqa: BLE001
            if not self._quit.is_set():
                self.errors.append(f"event stream: {e!r}")

    # -- the senders -----------------------------------------------------

    def _send(self) -> None:
        from nomad_tpu.api.client import APIError

        while not self._quit.is_set():
            try:
                client = self._free.get(timeout=0.1)
            except queue.Empty:
                continue
            if self._closing.is_set():
                continue
            try:
                prev = self._last_of.get(client)
                if (self.workload.get("stop_when_done") and prev is not None
                        and not prev.stopped):
                    prev.stopped = True
                    self.api.jobs.deregister(prev.id, purge=True)
                with self._lock:
                    seq = self._next
                    self._next += 1
                rec = self._record(seq)
                rec.client = client
                self._last_of[client] = rec
                self.jobs[rec.id] = rec
                rec.t_send = time.monotonic()
                try:
                    res = self.api.jobs.register(rec.body)
                except APIError as e:
                    rec.refused = str(e)
                    res = {}
                rec.t_ack = time.monotonic()
                rec.acked = bool(res.get("EvalID"))
                if not rec.acked and rec.refused is None:
                    rec.refused = f"no EvalID in {res}"
                if not rec.acked and client != LONE:
                    self._free.put(client)
            except Exception as e:                  # noqa: BLE001
                self.errors.append(f"sender: {e!r}")
                return

    def _record(self, seq: int) -> JobRecord:
        if seq < len(self.deck):
            return self.deck[seq]
        # past the deck: the same jobs again under new ids
        base = self.deck[seq % len(self.deck)]
        lap = seq // len(self.deck)
        plain = dict(base.plain, id=f"{base.id}-lap{lap}")
        body = dict(base.body, ID=plain["id"], Name=plain["id"])
        return JobRecord(seq, plain, body)

    # -- control ---------------------------------------------------------

    def start_listener(self) -> None:
        t = threading.Thread(target=self._listen, name="bench-events",
                             daemon=True)
        t.start()
        self._threads.append(t)
        if not self.stream_ready.wait(30.0):
            raise RuntimeError("event stream did not open in 30 s")

    def send_one_and_wait(self, timeout_s: float) -> JobRecord:
        """One job alone (PR 21: a cold first wave builds the cluster
        tensors once per member)."""
        before = self._next
        sender = threading.Thread(target=self._send, name="bench-send-0",
                                  daemon=True)
        sender.start()
        self._threads.append(sender)
        self._free.put(LONE)
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            rec = self._last_of.get(LONE)
            if rec is not None and rec.t_done is not None:
                return rec
            if self.errors:
                raise RuntimeError("; ".join(self.errors))
            time.sleep(0.01)
        raise RuntimeError(
            f"the first job was not done in {timeout_s:.0f} s "
            f"(sent {self._next - before})")

    def start_clients(self) -> None:
        for i in range(1, self.workload.get("senders", 4)):
            t = threading.Thread(target=self._send, name=f"bench-send-{i}",
                                 daemon=True)
            t.start()
            self._threads.append(t)
        for client in range(self.workload["outstanding"]):
            self._free.put(client)

    def next_done(self, after: float, timeout_s: float) -> float:
        """The instant of the first job done at or after ``after``, or
        ``after + timeout_s`` where none is done by then."""
        i = 0
        while True:
            times = self.done_times
            while i < len(times):
                if times[i] >= after:
                    return times[i]
                i += 1
            if time.monotonic() >= after + timeout_s:
                return after + timeout_s
            time.sleep(0.001)

    def close(self) -> None:
        """Stop registering; what is in flight goes on."""
        self._closing.set()

    def in_flight(self) -> list:
        return [r for r in list(self.jobs.values())
                if r.acked and r.t_done is None]

    def quit(self) -> None:
        self._quit.set()
        for t in self._threads:
            if t.name != "bench-events":
                t.join(5.0)
