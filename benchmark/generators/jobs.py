"""One job shape of a configuration file -> the job a client registers.

A shape is data: ``{"kind", "share", "type", "cpu", "mem", "count":
[lo, hi], "priority": [lo, hi), "spread": null | attribute | [attributes], "distinct_hosts",
"gpu", "node_class"}``. ``make_job`` returns the program's ``Job``
struct (the SDK's input) and the plain record the reference and the
roofline read; nothing else of the benchmark touches the struct.
"""

from __future__ import annotations


def deck(shapes: list, size: int) -> list:
    """``size`` (shape index, count) pairs in the shares of ``shapes``
    by largest remainder, every shape at least once, the counts of a
    shape stepping evenly through its range: the same multiset of work
    for every seed, which only orders it."""
    total = sum(s["share"] for s in shapes)
    exact = [size * s["share"] / total for s in shapes]
    counts = [max(int(x), 1) for x in exact]
    order = sorted(range(len(shapes)), key=lambda i: exact[i] - int(exact[i]),
                   reverse=True)
    i = 0
    while sum(counts) < size:
        counts[order[i % len(shapes)]] += 1
        i += 1
    while sum(counts) > size:
        counts[max(range(len(shapes)), key=counts.__getitem__)] -= 1
    cards = []
    for si, n in enumerate(counts):
        lo, hi = shapes[si]["count"]
        span = hi - lo + 1
        cards.extend((si, lo + (j * span) // n) for j in range(n))
    return cards


def make_job(shape: dict, job_id: str, count: int, rng, cluster: dict):
    """The job struct and its plain record."""
    from nomad_tpu import mock, structs
    from nomad_tpu.structs import consts

    job = mock.simple_job(id=job_id, name=job_id)
    job.datacenters = list(cluster["job_datacenters"])
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources = structs.Resources(
        cpu=shape["cpu"], memory_mb=shape["mem"])
    spread = shape.get("spread")
    if isinstance(spread, list):
        spread = spread[int(rng.integers(0, len(spread)))]
    if shape.get("type") == "batch":
        job.type = consts.JOB_TYPE_BATCH
    if shape.get("priority"):
        lo, hi = shape["priority"]
        job.priority = int(rng.integers(lo, hi))
    if spread:
        tg.spreads = [structs.Spread(attribute=spread, weight=50)]
    if shape.get("distinct_hosts"):
        tg.constraints = list(tg.constraints) + [
            structs.Constraint(operand=consts.CONSTRAINT_DISTINCT_HOSTS)]
    if shape.get("node_class"):
        job.constraints = list(job.constraints) + [structs.Constraint(
            ltarget="${node.class}", rtarget=shape["node_class"],
            operand="=")]
    if shape.get("gpu"):
        tg.tasks[0].resources.devices = [structs.RequestedDevice(
            name=cluster["gpu_device"], count=shape["gpu"])]
    record = {
        "id": job_id, "kind": shape["kind"], "count": count,
        "cpu": float(shape["cpu"]), "mem": float(shape["mem"]),
        "disk": float(tg.ephemeral_disk.size_mb),
        "gpu": float(shape.get("gpu") or 0), "spread": spread,
        "distinct_hosts": bool(shape.get("distinct_hosts")),
        "node_class": shape.get("node_class") or "",
        "datacenters": list(cluster["job_datacenters"]),
    }
    return job, record
