"""``run.py`` end to end on the CPU at the tiny presets: the rest of a
run behind the look for a chip, sound and with the timed path broken
underneath; and the refusal off the chip."""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from benchmarks import loader, run

ROOT = loader.ROOT


def cell_run(tiny_root, workload, trace=False, **kwargs):
    cell = loader.Cell(workload, root=tiny_root)
    return run.run_cell(cell, 4_000_000_007, 0.3, trace, jax.devices(),
                        **kwargs)


@pytest.mark.parametrize('workload', [
    'tiny-starcoder2.train', 'tiny-mpt.train4', 'tiny-mpt.decode',
    'tiny-starcoder2.decode'])
def test_sound_run_is_correct(tiny_root, workload):
    line = cell_run(tiny_root, workload)
    assert line['correct'] is True and line['failed'] == 0
    assert line['attempted'] > 0
    assert set(line['metrics']) >= {'setup_s'}
    assert all(np.isfinite(m['value']) and m['value'] > 0
               for m in line['metrics'].values())
    json.dumps(line)


def unchanged_state(step):
    """A train step that returns its state as it was given."""
    def broken(params, opt_state, batch):
        loss = step(jax.tree.map(lambda x: x.copy(), params),
                    jax.tree.map(lambda x: x.copy(), opt_state), batch)[2]
        return params, opt_state, loss
    return broken


def half_the_batch(step):
    """A train step that leaves out the second half of the sequence's
    targets."""
    def broken(params, opt_state, batch):
        tokens, targets = batch
        half = targets.shape[-1] // 2
        return step(params, opt_state,
                    (tokens, targets.at[..., half:].set(-1)))
    return broken


def altered_token(step):
    """A decode step that alters the token where it is produced."""
    def broken(params, tok, caches):
        caches, nxt, ok = step(params, tok, caches)
        return caches, (nxt + 1) % 128, ok
    return broken


@pytest.mark.parametrize('workload, wrapper, number', [
    ('tiny-mpt.train', unchanged_state, 'update_norm_gap'),
    ('tiny-starcoder2.train', half_the_batch, 'loss_gap.step1'),
    ('tiny-mpt.decode', altered_token, 'served_logit_gap'),
])
def test_broken_timed_path_is_not_correct(tiny_root, workload, wrapper,
                                          number, capsys):
    line = cell_run(tiny_root, workload, step_wrapper=wrapper)
    assert line['correct'] is False
    rows = [json.loads(x) for x in capsys.readouterr().out.splitlines()
            if x.startswith('{"compared"')]
    assert number in {r['compared'] for r in rows if not r['ok']}


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    out = subprocess.run(
        [sys.executable, 'benchmarks/run.py', '--workload',
         'mpt-7b.train-16k', '--seed', '1', '--seconds', '1',
         '--trace', '0'],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 2
    assert out.stdout.strip() == ''
    assert 'TPU' in out.stderr
