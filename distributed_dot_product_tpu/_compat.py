# -*- coding: utf-8 -*-
"""Virtual CPU device provisioning for tests, CI gates and CPU-mesh
subprocesses (the name is historical: this module once bridged older
JAX releases; the code now targets the installed JAX only)."""

import jax

__all__ = ['ensure_cpu_devices']


def ensure_cpu_devices(n, force_cpu=True):
    """Provision an ``n``-wide virtual CPU platform. Must run before the
    backend initializes (the first ``jax.devices()``/computation)."""
    if force_cpu:
        jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', n)
