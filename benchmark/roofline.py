"""The bytes and operations a placement needs, from shapes alone.

What is counted is the ALGORITHM's work, whatever implements it: one
placement step scores every real node of the cluster for one more
allocation of a job, so it has to read, once, each per-node plane the
job's features need, and do the arithmetic of feasibility and score on
them. Padding steps and padding nodes are not work. The counts of real
steps, real nodes and features come from the harness's own record of
the jobs it sent, not from the program.

Per node and step, float32 planes (4 bytes):

- every job: capacity and usage in cpu, memory and disk (6 planes) and
  the feasibility mask of its constraints and datacenters (1);
- anti-affinity and distinct_hosts: the job's own count on the node (1);
- a device ask: free device instances (1);
- a spread: the node's bucket (1).

Operations per node and step: 3 fit comparisons, 2 divisions, 2
subtractions and 2 powers for binpack, the clip and normalisation (4),
the mean of planes (2), the mask and arg-max (3): 18, and 4 more for
each of anti-affinity, device and spread planes that apply. The chip's
table has one peak for operations (bf16 on the MXU); this work is
float32 on the vector unit, so the operations' bound is generous and
the bytes' bound is the one that holds.
"""

from __future__ import annotations

BYTES_PER_PLANE = 4
BASE_PLANES = 7
BASE_OPS = 18
EXTRA_OPS = 4


def step_cost(job: dict, n_nodes: int) -> tuple:
    """(bytes, operations) of one placement step of ``job``."""
    extra = 1                                   # own count: anti-affinity
    extra += 1 if job.get("gpu") else 0
    extra += 1 if job.get("spread") else 0
    planes = BASE_PLANES + extra
    return (planes * BYTES_PER_PLANE * n_nodes,
            (BASE_OPS + EXTRA_OPS * extra) * n_nodes)


def least_seconds(jobs: list, n_nodes: int, peak: dict) -> dict:
    """The least time the chip could take for every step of ``jobs``
    (plain job records), and which bound it is."""
    total_bytes = total_ops = 0
    steps = 0
    for job in jobs:
        b, o = step_cost(job, n_nodes)
        total_bytes += b * job["count"]
        total_ops += o * job["count"]
        steps += job["count"]
    by_bytes = total_bytes / peak["bytes_per_s"]
    by_ops = total_ops / peak["flops_per_s"]
    return {"seconds": max(by_bytes, by_ops), "steps": steps,
            "bytes": total_bytes, "operations": total_ops,
            "bound": "bandwidth" if by_bytes >= by_ops else "compute"}


def peak_of(peaks: dict, device_kind: str) -> dict:
    if device_kind not in peaks:
        raise KeyError(f"peaks.json has no device kind {device_kind!r}")
    return peaks[device_kind]
