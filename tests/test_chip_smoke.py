# -*- coding: utf-8 -*-
"""
The chip harness must REFUSE off-chip, never fall back; and the compile
cache goes to one place: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX
reads it, the code sets nothing), else ``<checkout>/.jax_cache``.
"""

import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from distributed_dot_product_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(*argv, env=None):
    env = dict(os.environ if env is None else env, JAX_PLATFORMS='cpu')
    return subprocess.run([sys.executable, *argv], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=600)


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them (the
    session's own cache setting must not move under the other tests)."""
    calls = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_env_var_wins_and_nothing_is_set(
        monkeypatch, config_updates):
    monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', '/some/where/else')
    assert compile_cache.setup_compile_cache() == '/some/where/else'
    assert config_updates == []


def test_compile_cache_default_is_fixed_path_in_checkout(
        monkeypatch, config_updates):
    monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
    want = os.path.join(REPO, '.jax_cache')
    assert compile_cache.setup_compile_cache() == want
    assert compile_cache.setup_compile_cache() == want
    assert config_updates == [('jax_compilation_cache_dir', want)] * 2
    # Another process (another pid) lands on the same directory.
    env = {k: v for k, v in os.environ.items()
           if k != 'JAX_COMPILATION_CACHE_DIR'}
    proc = _run('-c', 'import jax; from distributed_dot_product_tpu.utils'
                '.compile_cache import setup_compile_cache as s; '
                'print(s()); print(jax.config.jax_compilation_cache_dir)',
                env=env)
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.split() == [want, want]


def test_chip_smoke_refuses_without_accelerator():
    """No arguments, no chip: non-zero exit and NO result on stdout."""
    proc = _run('chip_smoke.py')
    assert proc.returncode not in (0, None)
    assert proc.stdout.strip() == ''
    assert 'no TPU' in proc.stderr


def test_chip_smoke_tiny_cpu_rehearsal_still_fails():
    """``--tiny`` walks every phase on the CPU — and must still end
    non-zero without ever printing ``"ok": true``: the size option
    relaxes no check (the kernels did not compile for a chip, the
    decode impl did not resolve to ``kernel``, the platform is not
    ``tpu``)."""
    proc = _run('chip_smoke.py', '--tiny')
    assert proc.returncode == 1, proc.stderr[-3000:]
    assert '"ok": true' not in proc.stdout
    lines = [json.loads(line) for line in proc.stdout.splitlines()]
    assert lines[-1] == {'ok': False, 'device': lines[0]['device']}
    phases = {rec['phase']: rec for rec in lines if 'phase' in rec}
    assert set(phases) == {'train', 'generate', 'generate_latent',
                           'generate_mixed', 'generate_hybrid',
                           'generate_sparse', 'generate_ling',
                           'generate_conv', 'serve'}
    # a recurrent state beside a slab: the request after a restore reads
    # what the first did
    hybrid = phases['generate_hybrid']
    assert hybrid['hybrid_caches'] == ['NoneType', 'StateCache',
                                       'DecodeCache']
    assert hybrid['checks']['hybrid.restored_request_agrees'] is True
    # a block-sparse layer beside a Lightning state: every served step
    # picks its topk blocks, and its form is printed beside the counters
    sparse = phases['generate_sparse']
    assert sparse['sparse_caches'] == ['SparseCache', 'StateCache']
    assert sparse['sparse_decode'] == [
        {'impl': 'xla', 'picks': 2, 'topk': 2, 'group': 2,
         'select': 'sort'}]
    assert sparse['checks']['sparse.every_step_picks_topk'] is True
    assert sparse['checks']['sparse.restored_request_agrees'] is True
    # a latent cache beside a delta-rule state: the step's forms, the
    # expert routes and the rows routed to the held group are printed
    ling = phases['generate_ling']
    assert ling['ling_caches'] == ['StateCache', 'LatentCache']
    assert ling['ling_mla_decode'] == ['xla:latent']
    assert [f['form'] for f in ling['ling_delta_step']] == ['xla']
    assert [(r['route'], r['select'])
            for r in ling['ling_expert_routes']] == 2 * [
                ('hit_list', 'sort')]
    assert ling['checks']['ling.picks_are_top_k_s'] is True
    assert np.asarray(ling['ling_group_rows']).shape == (3, 2)
    assert ling['checks']['ling.restored_request_agrees'] is True
    assert ling['checks']['ling.latent_rows_grew'] is True
    # a window-only recurrent layer beside the packed slab: both forms
    # and the expert calls' routes are printed
    conv = phases['generate_conv']
    assert conv['conv_caches'] == ['StateCache', 'StateCache',
                                   'PackedCache']
    assert conv['conv_slab_step'] == [{
        'resolved': 'xla', 'cache': 'packed', 'token_bytes': None,
        'tail': None, 'heads_a_pass': None}]
    assert conv['conv_step_forms'] == 2 * [
        {'form': 'shift', 'taps': 3, 'channels': 128}]
    assert [(r['route'], r['bound_by'])
            for r in conv['conv_expert_routes']] == 2 * [
                ('hit_list', 'rule')]
    assert conv['checks']['conv.restored_request_agrees'] is True
    assert conv['checks']['conv.a_request_moves_the_window'] is True
    # both kernel modes side by side, off the chip both through XLA
    assert phases['generate_mixed']['mixed_caches'] == ['layer', 'ring']
    # one `build` line a phase, from the program's own ledger: seconds by
    # kind, the kernels' bodies by their Pallas names, the costliest
    # programs; nothing fell off the ledger's far end
    built = {rec['build']: rec for rec in lines if 'build' in rec}
    assert set(built) == set(phases)
    for name, rec in built.items():
        assert set(rec['seconds']) == {'trace', 'lower', 'compile',
                                       'build', 'cache_read'}
        assert rec['seconds']['trace'] > 0 and rec['records'] > 0
        assert rec['dropped'] == 0
        assert 1 <= len(rec['costliest']) <= 3
        assert sum(rec['seconds'].values()) <= phases[name]['seconds']
    assert {'flash_fwd', 'flash_bwd_fused'} <= set(
        built['train']['kernels'])
    assert 'moe_hit_experts' in built['generate_ling']['kernels']
    assert built['train']['seconds']['build'] > 0
    for name, rec in phases.items():
        assert 'error' not in rec, (name, rec.get('error'))
        failed = {k for k, v in rec['checks'].items() if not v}
        # Everything a CPU can get right is right; only the checks
        # that need the chip fail.
        assert failed and all(
            k.endswith(('tpu_custom_call', 'resolved_kernel',
                        'step_is_the_kernel'))
            for k in failed), (name, failed)
