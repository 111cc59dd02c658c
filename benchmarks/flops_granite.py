"""Operations and bytes that the decode step of a ``granitemoehybrid``
stack needs (Granite 4.0-H: a Mamba-2 or an attention mixer AND small
gated experts beside a shared MLP in every layer), from the
configuration's shapes alone (``flops.py``'s rules: a multiply-add is
two operations, only needed work is counted; the same work whatever
implements it). Kept with the benchmark so that no PR that claims a gain
can change the yardstick.
"""

BYTES = 2       # bfloat16 weights, K/V and convolution window
STATE_BYTES = 4     # the recurrent state is float32


def layer_kinds(config):
    """The layers held, each ``'mamba'`` or ``'attention'``."""
    return config['layer_types'][:config['num_hidden_layers']]


def head_dim(config):
    return config['hidden_size'] // config['num_attention_heads']


def conv_channels(config):
    return (config['mamba_n_heads'] * config['mamba_d_head']
            + 2 * config['mamba_n_groups'] * config['mamba_d_state'])


def state_elements(config):
    return (config['mamba_n_heads'] * config['mamba_d_head']
            * config['mamba_d_state'])


def state_bytes(config):
    """One session's state and convolution window in one recurrent
    layer."""
    window = (config['mamba_d_conv'] - 1) * conv_channels(config)
    return state_elements(config) * STATE_BYTES + window * BYTES


def ssm_step(config, batch):
    """The recurrent layers' pass over their states in one token step:
    every state and window read once and written once; an element of
    the state takes a multiply by the decay, a multiply-add of the outer
    product and a multiply-add into the read against C."""
    layers = layer_kinds(config).count('mamba')
    return {'bytes': layers * batch * 2 * state_bytes(config),
            'flops': layers * batch * 5 * state_elements(config)}


def attn_decode_step(config, batch, context):
    """The attention layers' decode kernel: the new row attends itself
    and all ``context`` rows before it; every K and V row read once for
    its KV head's whole query group, and the new row written."""
    layers = layer_kinds(config).count('attention')
    kv, heads = config['num_key_value_heads'], config['num_attention_heads']
    d, rows = head_dim(config), context + 1
    return {'bytes': layers * batch * kv * 2 * d * BYTES * (rows + 1),
            'flops': layers * batch * heads * 4 * d * rows}


def expert_bytes(config):
    """One routed expert's three matrices (the fused input matrix's two
    halves and the output matrix)."""
    return 3 * config['hidden_size'] * config['intermediate_size'] * BYTES


def experts_held(config):
    lo, hi = config['experts_held']
    return hi - lo


def expected_distinct_held(config, tokens):
    """Distinct HELD experts that ``tokens`` uniform top-k picks over
    the router's whole width hit in one layer: ``held (1 - (1 -
    k/E)^tokens)``."""
    e = config['published']['num_local_experts']
    k = config['num_experts_per_tok']
    return experts_held(config) * (1.0 - (1.0 - k / e) ** tokens)


def cache_gib(caches):
    """``{'full_gib', 'state_gib'}``: the bytes of the buffers that
    ``make_decode_caches`` built, K and V of the layers whose cache
    grows and state + window of the recurrent ones."""
    out = {'full_gib': 0.0, 'state_gib': 0.0}
    for cache in caches:
        if hasattr(cache, 'state'):
            out['state_gib'] += (cache.state.nbytes
                                 + cache.conv.nbytes) / 2.0 ** 30
        else:
            out['full_gib'] += (cache.k.nbytes + cache.v.nbytes) / 2.0 ** 30
    return out
