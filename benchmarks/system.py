"""The system under test, built from a configuration file. Everything
the benchmark takes from the program passes through here or a driver."""

import jax.numpy as jnp

from benchmarks.weights import model_sizes


def build_lm(config, weight_quant=None, **attn_overrides):
    """``TransformerLM`` at the configuration's sizes, computing in the
    type the configuration states (bfloat16 in every cell): a scanned
    stack with full remat (the form that fits the cells' depths), the
    LM's default flash attention.
    ``weight_quant`` and ``attn_overrides`` are for reading the
    program's own lower-precision paths (``tools/readings.py``)."""
    from distributed_dot_product_tpu import TransformerLM
    s = model_sizes(config)
    return TransformerLM(
        vocab_size=s['vocab_size'], dim=s['dim'], num_heads=s['num_heads'],
        n_layers=s['n_layers'], mlp_ratio=s['mlp_ratio'],
        dtype=jnp.dtype(config['precision']['compute']),
        weight_quant=weight_quant, scan_layers=True, remat=True,
        attn_kwargs={**s['attn_kwargs'], **attn_overrides})
