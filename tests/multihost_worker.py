# -*- coding: utf-8 -*-
"""
Worker process for the multi-host launch test (run by test_multihost.py).

Each OS process simulates one host: it owns ``LOCAL_DEVICES`` virtual CPU
devices and joins the others through ``comm.init`` /
``jax.distributed.initialize`` — the TPU-native replacement for the
reference's ``horovodrun -np N --mpi`` process launch (reference
README.md:77,173-176). The joined processes form ONE global mesh and run
ONE SPMD train step on deterministic data; process 0 prints the loss,
which the test compares against the identical single-process run.

Usage: python multihost_worker.py <process_id> <num_processes> <port>
"""

import sys

import jax

LOCAL_DEVICES = 4


def make_batch(batch, t, dim):
    """Deterministic batch — identical in every process and in the
    single-process oracle, with no dependence on device topology."""
    import numpy as np
    base = np.arange(batch * t * dim, dtype=np.float32)
    x = (np.sin(base * 0.01).reshape(batch, t, dim) * 0.5).astype(np.float32)
    target = (np.cos(base * 0.02).reshape(batch, t, dim) * 0.5
              ).astype(np.float32)
    mask = np.zeros((batch, t, t), dtype=bool)
    return x, target, mask


def run_step(world, ckpt_dir=None):
    """Build the model/mesh/step and run one training step on global
    arrays; returns the (fully-replicated) loss as a float. With
    ``ckpt_dir``, also saves the post-step state and restores it — the
    collective multi-host checkpoint path (every process participates)."""
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_dot_product_tpu import DistributedDotProductAttn
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    from distributed_dot_product_tpu.train import make_train_step

    mesh = seq_mesh(world)
    batch, t, dim, heads = 2, world * 4, 32, 4
    x_np, target_np, mask_np = make_batch(batch, t, dim)

    act = NamedSharding(mesh, P(None, 'seq', None))
    mask_sh = NamedSharding(mesh, P(None, 'seq', None))

    def globalize(np_arr, sharding):
        return jax.make_array_from_callback(
            np_arr.shape, sharding, lambda idx: np_arr[idx])

    x = globalize(x_np, act)
    target = globalize(target_np, act)
    mask = globalize(mask_np, mask_sh)

    model = DistributedDotProductAttn(key_dim=dim, num_heads=heads, offset=2)
    # Init on host-local (replicated) data — identical in every process —
    # then commit the params to the mesh as fully-replicated global arrays.
    params_local = model.init(jax.random.key(1),
                              jnp_like(x_np), jnp_like(x_np), jnp_like(x_np),
                              jnp_like(mask_np))
    rep = NamedSharding(mesh, P())
    params = jax.tree.map(
        lambda p: globalize(np.asarray(p), rep), params_local)

    optimizer = optax.adam(1e-3)
    opt_state = jax.tree.map(
        lambda p: globalize(np.asarray(p), rep) if hasattr(p, 'shape') else p,
        optimizer.init(params_local))

    step = make_train_step(model, optimizer, mesh, donate=False)
    new_params, new_opt, loss = step(params, opt_state,
                                     (x, x, x, mask, target))

    if ckpt_dir is not None:
        # Collective save + restore across all processes (the checkpoint
        # module's multi-host contract): every process calls with its view
        # of the same global arrays; restored leaves adopt the template's
        # (mesh-committed) shardings and must round-trip bitwise.
        import distributed_dot_product_tpu as ddp
        ddp.save(ckpt_dir, ddp.TrainState(1, new_params, new_opt))
        restored = ddp.restore(
            ckpt_dir, ddp.TrainState(0, new_params, new_opt))
        assert restored.step == 1
        for got_tree, want_tree in ((restored.params, new_params),
                                    (restored.opt_state, new_opt)):
            for a, b in zip(jax.tree.leaves(got_tree),
                            jax.tree.leaves(want_tree)):
                got = np.asarray(jax.device_get(a))
                want = np.asarray(jax.device_get(b))
                assert (got == want).all(), 'checkpoint round-trip mismatch'

    return float(np.asarray(jax.device_get(loss)))


def jnp_like(np_arr):
    import jax.numpy as jnp
    return jnp.asarray(np_arr)


def main():
    process_id, num_processes, port = (int(sys.argv[1]), int(sys.argv[2]),
                                       sys.argv[3])
    ckpt_dir = sys.argv[4] if len(sys.argv) > 4 else None
    # Virtual CPU devices (must run before backend init).
    from distributed_dot_product_tpu._compat import ensure_cpu_devices
    ensure_cpu_devices(LOCAL_DEVICES)

    from distributed_dot_product_tpu.utils import comm
    comm.init(coordinator_address=f'127.0.0.1:{port}',
              num_processes=num_processes, process_id=process_id)
    assert jax.process_count() == num_processes, jax.process_count()
    world = num_processes * LOCAL_DEVICES
    assert len(jax.devices()) == world, jax.devices()

    loss = run_step(world, ckpt_dir=ckpt_dir)
    comm.synchronize()
    if comm.is_main_process():
        print(f'MULTIHOST_LOSS={loss:.10f}', flush=True)


if __name__ == '__main__':
    main()
