"""Operations and bytes that the decode step of an ``lfm2_moe`` stack
needs (LFM2-8B-A1B: a gated short-convolution or a GQA mixer, then a
dense gated MLP in the leading layers and gated experts with no shared
one in the rest), from the configuration's shapes alone (``flops.py``'s
rules: a multiply-add is two operations, only needed work is counted;
the same work whatever implements it — a cache that pads a 64-wide head
to a lane tile streams twice these bytes and reads under 50 %). Kept
with the benchmark so that no PR that claims a gain can change the
yardstick.
"""

BYTES = 2       # bfloat16 weights, K/V and convolution windows


def layer_kinds(config):
    """The layers held, each ``'conv'`` or ``'attn'``."""
    return ['attn' if kind == 'full_attention' else 'conv'
            for kind in config['layer_types'][:config['num_hidden_layers']]]


def expert_layers(config):
    return list(range(config['num_dense_layers'],
                      config['num_hidden_layers']))


def head_dim(config):
    return config['hidden_size'] // config['num_attention_heads']


def window_bytes(config):
    """One session's window in one conv layer: the last ``conv_L_cache -
    1`` rows of ``u``."""
    return (config['conv_L_cache'] - 1) * config['hidden_size'] * BYTES


def conv_step(config, batch):
    """The conv layers' pass over their windows in one token step: every
    window read once and written once beside the two projections'
    weights (read once a step whatever the batch); a channel takes a
    multiply for each gate, a multiply-add a tap."""
    layers = layer_kinds(config).count('conv')
    d, taps = config['hidden_size'], config['conv_L_cache']
    weights = (3 * d * d + d * d + taps * d) * BYTES
    return {'bytes': layers * (weights + batch * 2 * window_bytes(config)),
            'flops': layers * batch * (2 * 4 * d * d + (2 * taps + 2) * d)}


def attn_decode_step(config, batch, context):
    """The GQA layers' decode kernel: the new row attends itself and all
    ``context`` rows before it; every K and V row read once for its KV
    head's whole query group, and the new row written — ``head_dim``
    values each, whatever the layout pads them to."""
    layers = layer_kinds(config).count('attn')
    kv, heads = config['num_key_value_heads'], config['num_attention_heads']
    d, rows = head_dim(config), context + 1
    return {'bytes': layers * batch * kv * 2 * d * BYTES * (rows + 1),
            'flops': layers * batch * heads * 4 * d * rows}


def expert_bytes(config):
    """One routed expert's three matrices."""
    return (3 * config['hidden_size'] * config['moe_intermediate_size']
            * BYTES)


def parameters(config):
    """Every parameter of the stack as it is held (the tied table
    once)."""
    d, v = config['hidden_size'], config['vocab_size']
    kv = config['num_key_value_heads'] * head_dim(config)
    experts = (config['num_experts'] * 3 * d
               * config['moe_intermediate_size']
               + d * config['num_experts'] + config['num_experts'])
    mixers = {'conv': 3 * d * d + config['conv_L_cache'] * d + d * d,
              'attn': 2 * d * d + 2 * d * kv + 2 * head_dim(config)}
    total = v * d + d                              # the table, ln_f
    for i, kind in enumerate(layer_kinds(config)):
        total += mixers[kind] + 2 * d              # the mixer, ln1, ln2
        total += (experts if i in expert_layers(config)
                  else 3 * d * config['intermediate_size'])
    return total


def cache_gib(caches):
    """``{'full_gib', 'state_gib'}``: the bytes of the buffers that
    ``make_decode_caches`` built, the K/V of the layers whose cache
    grows (one packed buffer, or a K and a V buffer) and the windows of
    the conv layers."""
    out = {'full_gib': 0.0, 'state_gib': 0.0}
    for cache in caches:
        if hasattr(cache, 'state'):
            out['state_gib'] += (cache.state.nbytes
                                 + cache.conv.nbytes) / 2.0 ** 30
        elif hasattr(cache, 'kv'):
            out['full_gib'] += cache.kv.nbytes / 2.0 ** 30
        else:
            out['full_gib'] += (cache.k.nbytes + cache.v.nbytes) / 2.0 ** 30
    return out
