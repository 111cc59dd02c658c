# -*- coding: utf-8 -*-
"""
Multi-host launch-path test.

The reference's multi-node story is ``horovodrun -np N --mpi python ...``
(reference README.md:77,173-176): N OS processes, one GPU each, joined by
MPI. The TPU-native equivalent is one process per host joined by
``jax.distributed.initialize`` (wrapped by ``comm.init``), after which the
same SPMD programs run unchanged over the global mesh.

This test actually exercises that path: it spawns 2 localhost processes
("hosts") of 4 virtual CPU devices each, has them form one 8-device mesh,
runs ONE full training step, and checks the loss equals the identical
single-process 8-device run — proving the multi-host wiring changes
nothing about the math.
"""

import os
import subprocess
import sys

import numpy as np
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_HERE)


@pytest.mark.slow
def test_two_process_mesh_matches_single_process(tmp_path):
    # Ephemeral port: bind-and-release so concurrent runs don't collide on
    # a fixed coordinator address.
    import socket
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        port = s.getsockname()[1]
    env = {k: v for k, v in os.environ.items()
           if k not in ('XLA_FLAGS', 'JAX_PLATFORMS')}
    env['PYTHONPATH'] = _REPO + os.pathsep + env.get('PYTHONPATH', '')
    worker = os.path.join(_HERE, 'multihost_worker.py')
    ckpt_dir = str(tmp_path / 'ckpt')
    procs = [
        subprocess.Popen(
            [sys.executable, worker, str(pid), '2', str(port), ckpt_dir],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            env=env, cwd=_REPO)
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=300)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f'worker failed:\n{out}'

    joined = '\n'.join(outs)
    line = [ln for ln in joined.splitlines()
            if ln.startswith('MULTIHOST_LOSS=')]
    assert line, joined
    multi_loss = float(line[0].split('=', 1)[1])

    # Single-process oracle on the conftest-provided 8-device CPU mesh.
    sys.path.insert(0, _HERE)   # plain `pytest` doesn't put tests/ on path
    from multihost_worker import run_step
    single_loss = run_step(8)
    np.testing.assert_allclose(multi_loss, single_loss, rtol=1e-6)

