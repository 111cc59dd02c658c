"""Operations and bytes that the decode step of a ``minicpm_sala`` stack
needs (MiniCPM-SALA: a learned block-sparse NoPE attention layer to
three Lightning linear-attention layers, a dense gated MLP in every
layer), from the configuration's shapes alone (``flops.py``'s rules: a
multiply-add is two operations, only needed work is counted; the same
work whatever implements it). Kept with the benchmark so that no PR that
claims a gain can change the yardstick.
"""

BYTES = 2       # bfloat16 weights, K/V and pooled keys
STATE_BYTES = 4     # the recurrent state is float32

SPARSE, LIGHTNING = 'minicpm4', 'lightning-attn'


def layer_kinds(config):
    """The layers held, each ``'minicpm4'`` or ``'lightning-attn'``."""
    return list(config['mixer_types'][:config['num_hidden_layers']])


def sparse_sizes(config):
    """``(kernel, stride, block, init_blocks, window, topk, dense_len)``
    of the selection, in cache rows."""
    s = config['sparse_config']
    return (s['kernel_size'], s['kernel_stride'], s['block_size'],
            s['init_blocks'], s['window_size'], s['topk'], s['dense_len'])


def picked_rows(config, context):
    """Valid cache rows a token at position ``context`` reads in one
    sparse layer and KV head, its own row included: every row up to
    ``dense_len``, else the rows of ``topk`` blocks, the token's own
    block as far as the token."""
    _, _, block, _, _, topk, dense_len = sparse_sizes(config)
    rows = context + 1
    if rows <= dense_len:
        return rows
    return (topk - 1) * block + context % block + 1


def pooled_rows(config, context):
    """Pooled rows complete once the token at ``context`` is written."""
    kernel, stride = sparse_sizes(config)[:2]
    return max((context + 1 - kernel) // stride + 1, 0)


def sparse_decode_step(config, batch, context):
    """The sparse layers' kernel: the valid rows of the picked blocks,
    K and V, read once for the KV head's whole query group, and the new
    row written."""
    layers = layer_kinds(config).count(SPARSE)
    kv, heads = config['num_key_value_heads'], config['num_attention_heads']
    d, rows = config['head_dim'], picked_rows(config, context)
    return {'bytes': layers * batch * kv * (rows + 1) * 2 * d * BYTES,
            'flops': layers * batch * heads * 4 * d * rows}


def sparse_select_step(config, batch, context):
    """The selection: every complete pooled row read once for the KV
    head's whole query group, one multiply-add a query head, channel and
    row."""
    layers = layer_kinds(config).count(SPARSE)
    kv, heads = config['num_key_value_heads'], config['num_attention_heads']
    d, rows = config['head_dim'], pooled_rows(config, context)
    return {'bytes': layers * batch * kv * rows * d * BYTES,
            'flops': layers * batch * heads * 2 * d * rows}


def state_elements(config):
    return config['lightning_nh'] * config['lightning_head_dim'] ** 2


def lightning_step(config, batch):
    """The Lightning layers' pass over their states in one token step:
    every state read once and written once; an element takes a multiply
    by the decay, a multiply-add of the outer product and a multiply-add
    into the read against q."""
    layers = layer_kinds(config).count(LIGHTNING)
    return {'bytes': layers * batch * 2 * state_elements(config)
            * STATE_BYTES,
            'flops': layers * batch * 5 * state_elements(config)}


def picked_rows_share(config, context):
    """Rows the sparse layers read over the rows valid, a step."""
    return picked_rows(config, context) / (context + 1)


def cache_gib(caches):
    """``{'full_gib', 'pooled_gib', 'state_gib'}``: the bytes of the
    buffers that ``make_decode_caches`` built — K and V of the layers
    whose cache grows, their pooled keys, and the recurrent layers'
    states."""
    out = {'full_gib': 0.0, 'pooled_gib': 0.0, 'state_gib': 0.0}
    for cache in caches:
        if hasattr(cache, 'state'):
            out['state_gib'] += (cache.state.nbytes
                                 + cache.conv.nbytes) / 2.0 ** 30
        else:
            out['full_gib'] += (cache.k.nbytes + cache.v.nbytes) / 2.0 ** 30
            out['pooled_gib'] += cache.pooled.nbytes / 2.0 ** 30
    return out
