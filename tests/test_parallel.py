"""Multi-chip sharding parity: the batched sharded kernel must produce
exactly the single-device results (GSPMD collectives change layout, not
semantics)."""

import jax
import numpy as np
import pytest

from nomad_tpu.ops.kernel import KernelOut, pad_steps, place_taskgroup_jit
from nomad_tpu.parallel.mesh import make_mesh
from nomad_tpu.parallel.sharded import (
    make_place_batch,
    stack_kernel_ins,
    unstack_kernel_outs,
)
from nomad_tpu.parallel.synthetic import synthetic_kernel_in


@pytest.fixture(scope="module")
def problems():
    n_steps = 4
    return n_steps, [
        synthetic_kernel_in(
            n_nodes=200, n_steps=n_steps, with_spread=(i % 2 == 0),
            used_frac=0.5, seed=i,
        )
        for i in range(4)
    ]


def test_mesh_shapes():
    mesh = make_mesh(8)
    assert mesh.shape == {"evals": 2, "nodes": 4}
    mesh = make_mesh(1)
    assert mesh.shape == {"evals": 1, "nodes": 1}
    mesh = make_mesh(8, evals_parallel=4)
    assert mesh.shape == {"evals": 4, "nodes": 2}


def test_sharded_matches_single_device(problems):
    n_steps, kins = problems
    k_pad = pad_steps(n_steps)
    singles = [
        KernelOut(*[np.asarray(x) for x in place_taskgroup_jit(kin, k_pad)])
        for kin in kins
    ]

    mesh = make_mesh(8)
    step = make_place_batch(mesh, k_pad)
    out = step(stack_kernel_ins(kins))
    jax.block_until_ready(out)
    outs = unstack_kernel_outs(out)

    for got, want in zip(outs, singles):
        np.testing.assert_array_equal(got.chosen, want.chosen)
        np.testing.assert_array_equal(got.found, want.found)
        np.testing.assert_allclose(got.scores, want.scores, rtol=1e-5)
        assert int(got.nodes_evaluated) == int(want.nodes_evaluated)
        assert int(got.nodes_feasible) == int(want.nodes_feasible)


def test_sharded_1d_nodes_only(problems):
    """A nodes-only mesh (evals axis 1) also runs: pure sp sharding."""
    n_steps, kins = problems
    k_pad = pad_steps(n_steps)
    mesh = make_mesh(8, evals_parallel=1)
    step = make_place_batch(mesh, k_pad)
    out = step(stack_kernel_ins(kins))
    jax.block_until_ready(out)
    found = np.asarray(out.found)
    assert found[:, :n_steps].all()


def test_fused_schedule_apply_step():
    """Device-resident state loop: placements commit as scatter deltas
    and later batches see them."""
    import jax.numpy as jnp

    from nomad_tpu.ops.kernel import KernelFeatures, build_kernel_in
    from nomad_tpu.parallel.batching import (
        device_put_shared,
        make_schedule_apply_step,
    )
    from nomad_tpu.parallel.synthetic import synthetic_cluster, synthetic_eval

    n_nodes, batch, k = 50, 4, 2
    cluster = synthetic_cluster(n_nodes, seed=1)
    ev = synthetic_eval(cluster, desired_count=k, seed=1)
    shared = device_put_shared(build_kernel_in(cluster, ev, k))
    lean = KernelFeatures(
        n_spreads=0, with_topk=False, with_devices=False, with_ports=False,
        with_cores=False, with_network=False, with_distinct=False,
        with_step_penalties=False, with_preferred=False,
    )
    step = make_schedule_apply_step(k, lean)

    uc = shared.used_cpu
    um = shared.used_mem
    ask_cpu = jnp.full(batch, 500.0, jnp.float32)
    ask_mem = jnp.full(batch, 256.0, jnp.float32)
    n_steps = jnp.full(batch, k, jnp.int32)

    total_cpu0 = float(uc.sum())
    out, uc, um = step(shared, uc, um, ask_cpu, ask_mem, n_steps)
    found = np.asarray(out.found)
    assert found.all()
    # every accepted placement committed 500 MHz
    assert float(uc.sum()) == pytest.approx(total_cpu0 + 500.0 * batch * k)
    # run again: utilization monotonically grows
    out2, uc2, um2 = step(shared, uc, um, ask_cpu, ask_mem, n_steps)
    assert float(uc2.sum()) == pytest.approx(total_cpu0 + 2 * 500.0 * batch * k)


class TestDonatedLoopOwnership:
    """The donated bench loops must never write into caller-owned
    numpy memory. ``jnp.asarray(numpy)`` is zero-copy on the CPU
    backend when the allocator cooperates; donating such a buffer let
    the runtime write the scan carry in place into the caller's array
    — a 1-in-5 top-k parity flake between two loops. The
    ``_jit_donating`` wrapper copies donated args into buffers it
    owns; this test re-runs a loop from the same numpy planes and
    must see identical results and untouched inputs every time."""

    def test_numpy_inputs_survive_donated_loop(self):
        import numpy as np
        import jax.numpy as jnp

        from nomad_tpu.ops.kernel import LEAN_FEATURES, build_kernel_in
        from nomad_tpu.parallel.batching import (
            device_put_shared,
            make_schedule_apply_loop,
        )
        from nomad_tpu.parallel.synthetic import (
            synthetic_cluster,
            synthetic_eval,
        )

        n, k, b = 200, 4, 4
        cluster = synthetic_cluster(n, cpu=2000.0, mem=4096.0,
                                    disk=50000.0, seed=11)
        ev = synthetic_eval(cluster, desired_count=k)
        shared = device_put_shared(build_kernel_in(cluster, ev, k))
        npad = shared.cap_cpu.shape[0]
        rng = np.random.default_rng(13)
        used = np.zeros(npad, np.float32)
        used[:n] = 2000.0 * 0.5 * rng.random(n, dtype=np.float32)
        usedm = np.zeros(npad, np.float32)
        usedm[:n] = 4096.0 * 0.5 * rng.random(n, dtype=np.float32)
        used0, usedm0 = used.copy(), usedm.copy()
        asks_cpu = jnp.asarray(
            rng.choice([100.0, 250.0], (3, b)).astype(np.float32))
        asks_mem = jnp.asarray(
            rng.choice([64.0, 128.0], (3, b)).astype(np.float32))
        n_steps = jnp.asarray(np.full(b, k, np.int32))

        loop = make_schedule_apply_loop(k, LEAN_FEATURES, topk=True)
        scores = set()
        for _ in range(4):
            out = loop(shared, jnp.asarray(used), jnp.asarray(usedm),
                       asks_cpu, asks_mem, n_steps)
            scores.add(float(out[0]))
            np.testing.assert_array_equal(used, used0)
            np.testing.assert_array_equal(usedm, usedm0)
        assert len(scores) == 1, "donated loop is not repeatable"
