"""Seeded weights, made on the device in one jitted call.

The tree has the layout ``TransformerLM(scan_layers=True)`` takes (a test
holds the two together), but its shapes come from the configuration file
alone, so the program and the plain reference are handed the same arrays
and neither has made them.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def model_sizes(config):
    """The sizes the program is built with, resolved from the
    configuration's ``program`` mapping (a string names a published key)."""
    def resolve(v):
        return config[v] if isinstance(v, str) else v
    prog = config['program']
    sizes = {k: resolve(v) for k, v in prog.items() if k != 'attn_kwargs'}
    attn = {}
    for k, v in prog['attn_kwargs'].items():
        if k == 'alibi_slopes':
            attn[k] = alibi_slopes(sizes['num_heads'],
                                   v['alibi_bias_max'])
        else:
            attn[k] = resolve(v)
    sizes['attn_kwargs'] = attn
    return sizes


def alibi_slopes(n_heads, bias_max):
    """MPT's ``gen_slopes`` for a power-of-two head count:
    ``2^(-bias_max * (i + 1) / n_heads)``."""
    if n_heads & (n_heads - 1):
        raise ValueError('alibi slopes here are for a power-of-two head '
                         f'count, got {n_heads}')
    return tuple(2.0 ** (-bias_max * (i + 1) / n_heads)
                 for i in range(n_heads))


def shapes(config):
    """``{path: (shape, fan_in or None)}`` of every leaf, layer-stacked."""
    s = model_sizes(config)
    d, h, n, v = s['dim'], s['num_heads'], s['n_layers'], s['vocab_size']
    kv = (s['attn_kwargs'].get('num_kv_heads') or h) * (d // h)
    hidden = s['mlp_ratio'] * d
    out = {('embed', 'embedding'): ((v, d), None),
           ('ln_f', 'scale'): ((d,), None), ('ln_f', 'bias'): ((d,), None)}
    block = ('stack', 'layers', 'block')
    dense = {('attn', 'keys'): (d, d), ('attn', 'queries'): (d, kv),
             ('attn', 'values'): (d, kv), ('attn', 'composition'): (d, d),
             ('mlp_in',): (d, hidden), ('mlp_out',): (hidden, d)}
    for path, (fan_in, fan_out) in dense.items():
        out[block + path + ('kernel',)] = ((n, fan_in, fan_out), fan_in)
        if path[0] != 'attn' or s['attn_kwargs'].get('add_bias'):
            out[block + path + ('bias',)] = ((n, fan_out), None)
    for ln in ('ln1', 'ln2'):
        out[block + (ln, 'scale')] = ((n, d), None)
        out[block + (ln, 'bias')] = ((n, d), None)
    return out


def seed_key(lo, hi, salt=0):
    """A key from a seed given as two halves that int32 holds (--seed may
    pass 2**31), traced, so that every seed runs one compiled program.
    ``rbg`` keys draw from the chip's generator: a 1.8 G-parameter tree
    takes seconds where threefry took a minute (chip run, PR 23)."""
    key = jax.random.key(lo, impl='rbg')
    return jax.random.fold_in(jax.random.fold_in(key, hi), salt)


def split_seed(seed):
    return np.int32(seed & 0x7fffffff), np.int32(seed >> 31)


def make(config, seed, dtype=jnp.float32, upcast=False):
    """The seeded parameter tree ``{'params': ...}``, rounded to
    ``dtype`` (the type the configuration serves or trains in);
    ``upcast`` hands the same rounded values back as float32, for the
    reference. Kernels are N(0, 1/fan_in), the embedding
    N(0, embedding_std^2), biases N(0, bias_std^2) (0 gives the zeros a
    no-bias model stands for), LayerNorm scales 1 + N(0, 0.02^2) so that
    a dropped scale shows. Leaves are drawn one after another (the
    barrier), so the peak is one leaf's, not the tree's."""
    init = config['init']
    table = shapes(config)

    @jax.jit
    def build(lo, hi):
        key = seed_key(lo, hi)
        tree = {}
        for i, (path, (shape, fan_in)) in enumerate(sorted(table.items())):
            k = jax.random.fold_in(key, i)
            if fan_in is not None:
                std = 1.0 / math.sqrt(fan_in)
            elif path[-1] == 'embedding':
                std = init['embedding_std']
            elif path[-1] == 'scale':
                std = 0.02
            else:
                std = init['bias_std']
            leaf = (std * jax.random.normal(k, shape, jnp.float32)
                    if std else jnp.zeros(shape, jnp.float32))
            if path[-1] == 'scale':
                leaf = leaf + 1.0
            node = tree
            for name in path[:-1]:
                node = node.setdefault(name, {})
            leaf = leaf.astype(dtype)
            leaf, key = jax.lax.optimization_barrier((leaf, key))
            node[path[-1]] = leaf.astype(jnp.float32) if upcast else leaf
        return {'params': tree}

    return build(*split_seed(seed))
