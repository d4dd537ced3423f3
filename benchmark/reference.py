"""The plain reference: Nomad's placement semantics in numpy, float64.

It imports nothing of the program and reads nothing the program made:
its cluster is the generator's plain record, its jobs are the harness's
own record of what it sent, and what it judges are the answers the API
served. The formulas are Nomad's (scheduler/rank.go, spread.go,
structs/funcs.go), written from their published definitions:

- binpack: ``20 - (10**free_cpu + 10**free_mem)`` clipped to [0, 18]
  and divided by 18, where ``free_x = 1 - (used + ask) / capacity``
  and capacity is the node's resources less its reserved ones;
- job anti-affinity: ``-(collisions + 1) / desired_count`` on a node
  that already holds ``collisions > 0`` allocations of the task group;
- even spread (no targets): from the counts of the job's allocations
  by attribute value, ``evenSpreadScoreBoost``;
- the final score is the mean of the planes that apply (a spread plane
  applies when it is not zero).

``dtype`` is float64 for the reference itself. The control computes the
same formulas in the nearest precision below the float32 the
configurations state, bfloat16, and has to fail.

``expected_score`` is one node at a time in any precision (the control's
path); ``scores_of_all`` and ``feasible_of_all`` are the same semantics
in float64 over every node of the cluster at once, which is what a
placement's node choice is held against.
"""

from __future__ import annotations

import numpy as np


def _cast(dtype):
    if dtype == "bfloat16":
        import ml_dtypes

        return ml_dtypes.bfloat16
    return np.dtype(dtype).type


def binpack(cap_cpu, cap_mem, util_cpu, util_mem, dtype="float64"):
    t = _cast(dtype)
    ten, one = t(10.0), t(1.0)
    free_cpu = one - t(util_cpu) / t(cap_cpu) if cap_cpu > 0 else t(0.0)
    free_mem = one - t(util_mem) / t(cap_mem) if cap_mem > 0 else t(0.0)
    total = t(np.power(ten, t(free_cpu))) + t(np.power(ten, t(free_mem)))
    fit = min(max(t(20.0) - t(total), t(0.0)), t(18.0))
    return t(fit) / t(18.0)


def even_spread_boost(counts: dict, value, dtype="float64"):
    """spread.go evenSpreadScoreBoost over the use map ``counts``
    (attribute value -> allocations of the job placed so far)."""
    t = _cast(dtype)
    used = {k: v for k, v in counts.items() if v > 0}
    if not used:
        return t(0.0)
    if value is None:
        return t(-1.0)
    current = used.get(value, 0)
    lo, hi = min(used.values()), max(used.values())
    if current != lo:
        return t(lo - current) / t(lo)
    if lo == hi:
        return t(-1.0)
    return t(hi - lo) / t(lo)


def expected_score(plain: dict, node: int, job: dict, prior_nodes: list,
                   spread_values=None, seen=(0.0, 0.0), dtype="float64"):
    """The final score Nomad records for placing one more allocation of
    ``job`` on ``node``, when the node holds its resident usage, the
    (cpu, mem) ``seen`` of other jobs placed since, and the
    ``prior_nodes`` (node indices) of the job's own earlier steps."""
    t = _cast(dtype)
    k = sum(1 for p in prior_nodes if p == node)
    util_cpu = plain["used_cpu"][node] + seen[0] + (k + 1) * job["cpu"]
    util_mem = plain["used_mem"][node] + seen[1] + (k + 1) * job["mem"]
    planes = [binpack(plain["cap_cpu"][node], plain["cap_mem"][node],
                      util_cpu, util_mem, dtype)]
    if k > 0:
        planes.append(-t(k + 1) / t(max(job["count"], 1)))
    if job["spread"]:
        counts: dict = {}
        for p in prior_nodes:
            v = spread_values[p]
            counts[v] = counts.get(v, 0) + 1
        boost = even_spread_boost(counts, spread_values[node], dtype)
        if boost != 0:
            planes.append(boost)
    total = t(0.0)
    for p in planes:
        total = t(total + t(p))
    return float(t(total) / t(len(planes)))


def spread_values_of(plain: dict, attribute: str, rack_attribute: str):
    """The per-node values of a spread ``attribute`` ("${...}")."""
    name = attribute.strip("${} ")
    if name == "node.datacenter":
        return plain["datacenter"]
    if name == rack_attribute:
        return plain["rack"]
    raise ValueError(f"reference knows no spread attribute {attribute!r}")


# -- every node of the cluster at once, float64 ---------------------------

#: the dimensions a node's capacity is held in
DIMS = ("cpu", "mem", "disk", "gpu")


def feasible_of_all(plain: dict, static_ok, used: dict, job: dict, own):
    """Which nodes can take one more allocation of ``job``: its
    datacenters and node class (``static_ok``, see ``static_mask``), room
    in every dimension given ``used`` (resident usage and everything
    placed since), and, under distinct_hosts, none of its own yet."""
    ok = static_ok.copy()
    for d in DIMS:
        ok &= used[d] + job[d] <= plain[f"cap_{d}"] + 1e-9
    if job["distinct_hosts"]:
        ok &= own == 0
    return ok


def static_mask(plain: dict, job: dict):
    ok = np.isin(np.array(plain["datacenter"]), job["datacenters"])
    if job["node_class"]:
        ok &= np.array(plain["node_class"]) == job["node_class"]
    return ok


def spread_boost_by_value(counts):
    """``even_spread_boost`` for every attribute value at once:
    ``counts[v]`` allocations of the job on value ``v`` so far. None
    where nothing is placed yet (the plane does not apply)."""
    used = counts[counts > 0]
    if used.size == 0:
        return None
    lo, hi = float(used.min()), float(used.max())
    current = counts.astype(np.float64)
    at_lo = -1.0 if lo == hi else (hi - lo) / lo
    return np.where(current != lo, (lo - current) / lo, at_lo)


def scores_of_all(plain: dict, used: dict, job: dict, own,
                  spread_codes=None, spread_counts=None):
    """The final score of one more allocation of ``job`` on every node:
    ``used`` holds cpu and mem in use before it, ``own`` the job's own
    allocations a node, ``spread_codes`` each node's attribute value as
    an index into ``spread_counts`` (-1: the node has none)."""
    cap_cpu, cap_mem = plain["cap_cpu"], plain["cap_mem"]
    with np.errstate(divide="ignore", invalid="ignore"):
        free_cpu = np.where(cap_cpu > 0,
                            1.0 - (used["cpu"] + job["cpu"]) / cap_cpu, 0.0)
        free_mem = np.where(cap_mem > 0,
                            1.0 - (used["mem"] + job["mem"]) / cap_mem, 0.0)
    total = np.power(10.0, free_cpu) + np.power(10.0, free_mem)
    score = np.clip(20.0 - total, 0.0, 18.0) / 18.0
    planes = np.ones(len(score))
    collide = own > 0
    score = score + np.where(
        collide, -(own + 1.0) / max(job["count"], 1), 0.0)
    planes += collide
    if job["spread"]:
        by_value = spread_boost_by_value(spread_counts)
        if by_value is not None:
            boost = np.where(spread_codes >= 0,
                             by_value[np.maximum(spread_codes, 0)], -1.0)
            score = score + boost
            planes += boost != 0
    return score / planes
