"""The upstream scheduler benchmark's cluster
(``scheduler/benchmarks/benchmarks_test.go``, ``upsertNodes``): ``nodes``
identical nodes, node ``i`` in rack ``i % racks`` and in datacenter
``i % len(datacenters)``, and nothing running on them: the mock node
with its resources replaced by the configuration's (``node_cpu_mhz``,
``node_memory_mb``, ``node_disk_mb``), its reserved ones kept. Only the
node ids come from the seed.
"""

from __future__ import annotations

import numpy as np

from .plain import new_plain, seeded_uuid


def build(cluster: dict, shapes: list, seed: int):
    """(snapshot bytes, plain record); ``shapes`` is not used: the
    grid's cluster holds no resident allocation."""
    from nomad_tpu import mock
    from nomad_tpu.state.store import StateStore

    n_nodes = cluster["nodes"]
    rng = np.random.default_rng(seed)
    dcs = cluster["job_datacenters"]
    where, key = cluster["rack_attribute"].split(".", 1)
    store = StateStore()
    plain = new_plain(n_nodes)
    for i in range(n_nodes):
        rack = f"r{i % cluster['racks']}"
        n = mock.node(id=seeded_uuid(rng), name=f"grid-{i}",
                      datacenter=dcs[i % len(dcs)])
        if where == "meta":
            n.meta = dict(n.meta or {})
            n.meta[key] = rack
        else:
            n.attributes = dict(n.attributes)
            n.attributes[key] = rack
        n.node_resources.cpu.cpu_shares = cluster["node_cpu_mhz"]
        n.node_resources.memory.memory_mb = cluster["node_memory_mb"]
        n.node_resources.disk.disk_mb = cluster["node_disk_mb"]
        n.compute_class()
        store.upsert_node(n)
        res, rsv = n.node_resources, n.reserved_resources
        plain["node_ids"][i] = n.id
        plain["datacenter"][i] = n.datacenter
        plain["rack"][i] = rack
        plain["cap_cpu"][i] = res.cpu.cpu_shares - rsv.cpu_shares
        plain["cap_mem"][i] = res.memory.memory_mb - rsv.memory_mb
        plain["cap_disk"][i] = res.disk.disk_mb - rsv.disk_mb
    return store.to_snapshot_bytes(), plain
