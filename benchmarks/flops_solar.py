"""Operations and bytes that the decode step of a ``solar_open2`` stack
needs (Solar Open 2: a gated delta-rule (KDA) or a gated NoPE GQA mixer
AND gated experts beside a shared one in every layer), from the
configuration's shapes alone (``flops.py``'s rules: a multiply-add is
two operations, only needed work is counted; the same work whatever
implements it). Kept with the benchmark so that no PR that claims a gain
can change the yardstick.
"""

BYTES = 2       # bfloat16 weights, K/V and convolution windows
STATE_BYTES = 4     # the recurrent state is float32


def layer_kinds(config):
    """The layers held, each ``'gqa'`` or ``'kda'``."""
    return ['gqa' if i in config['gqa_layers'] else 'kda'
            for i in range(config['num_hidden_layers'])]


def delta_sizes(config):
    """``(heads, head_dim, taps)`` of a KDA layer."""
    linear = config['linear_attn_config']
    return (linear['num_heads'], linear['head_dim'],
            linear['short_conv_kernel_size'])


def conv_channels(config):
    """q | k | v, each its own convolution: one window over all three."""
    heads, dim, _ = delta_sizes(config)
    return 3 * heads * dim


def state_elements(config):
    heads, dim, _ = delta_sizes(config)
    return heads * dim * dim


def state_bytes(config):
    """One session's state and convolution windows in one KDA layer."""
    window = (delta_sizes(config)[2] - 1) * conv_channels(config)
    return state_elements(config) * STATE_BYTES + window * BYTES


def delta_step(config, batch):
    """The KDA layers' pass over their states in one token step: every
    state and window read once and written once; an element of the state
    takes a multiply by the decay, a multiply-add into the reduction
    against k, a multiply-add of the correction and a multiply-add into
    the read against q."""
    layers = layer_kinds(config).count('kda')
    return {'bytes': layers * batch * 2 * state_bytes(config),
            'flops': layers * batch * 7 * state_elements(config)}


def attn_decode_step(config, batch, context):
    """The GQA layers' decode kernel: the new row attends itself and all
    ``context`` rows before it; every K and V row read once for its KV
    head's whole query group, and the new row written."""
    layers = layer_kinds(config).count('gqa')
    kv, heads = config['num_key_value_heads'], config['num_attention_heads']
    d, rows = config['head_dim'], context + 1
    return {'bytes': layers * batch * kv * 2 * d * BYTES * (rows + 1),
            'flops': layers * batch * heads * 4 * d * rows}


def expert_bytes(config):
    """One routed expert's three matrices."""
    return (3 * config['hidden_size'] * config['moe_intermediate_size']
            * BYTES)


def experts_held(config):
    lo, hi = config['experts_held']
    return hi - lo


def expected_distinct_held(config, tokens):
    """Distinct HELD experts that ``tokens`` uniform top-k picks over
    the router's whole width hit in one layer: ``held (1 - (1 -
    k/E)^tokens)``."""
    e = config['published']['n_routed_experts']
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
