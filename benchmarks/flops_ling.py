"""Operations and bytes that the decode step of a ``bailing_hybrid`` stack
needs (Ling 3.0 flash: five gated delta-rule (KDA) layers to one gated
latent-attention (MLA) layer, a dense gated MLP in the leading layers
and group-limited experts beside a shared one in the rest), from the
configuration's shapes alone (``flops.py``'s rules: a multiply-add is
two operations, only needed work is counted; the same work whatever
implements it). Kept with the benchmark so that no PR that claims a gain
can change the yardstick.
"""

BYTES = 2       # bfloat16 weights, latent rows and convolution windows
STATE_BYTES = 4     # the recurrent state is float32


def layer_kinds(config):
    """The layers held, one letter each: ``D`` (KDA mixer, dense MLP),
    ``K`` (KDA mixer, experts), ``A`` (MLA mixer, experts). The mixer
    goes by the layer's PUBLISHED index (``layers_held``: MLA where
    ``(i + 1) % layer_group_size == 0``), the feed-forward by its place
    here (the first ``first_k_dense_replace`` held layers are dense)."""
    held = config['layers_held']
    if len(held) != config['num_hidden_layers']:
        raise ValueError(f'layers_held {held} names '
                         f"{config['num_hidden_layers']} layers")
    out = []
    for j, i in enumerate(held):
        latent = (i + 1) % config['layer_group_size'] == 0
        dense = j < config['first_k_dense_replace']
        if latent and dense:
            raise ValueError('no kind for a latent layer with a dense MLP')
        out.append('A' if latent else 'D' if dense else 'K')
    return out


def expert_layers(config):
    return [i for i, kind in enumerate(layer_kinds(config)) if kind != 'D']


def delta_layers(config):
    return [i for i, kind in enumerate(layer_kinds(config)) if kind != 'A']


def delta_sizes(config):
    """``(heads, head_dim, taps)`` of a KDA layer
    (``num_kv_heads_for_linear_attn`` 0: as many key as value heads)."""
    return (config['num_attention_heads'], config['head_dim'],
            config['short_conv_kernel_size'])


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
    layers = len(delta_layers(config))
    return {'bytes': layers * batch * 2 * state_bytes(config),
            'flops': layers * batch * 7 * state_elements(config)}


def latent_row(config):
    """Values of one token's compressed row: ``[c_kv ; k_rope]``."""
    return config['kv_lora_rank'] + config['qk_rope_head_dim']


def mla_decode_step(config, batch, context):
    """The MLA layers' decode kernel: the new row attends itself and all
    ``context`` rows before it; every compressed row read ONCE for all
    heads (keys and values are the same bytes), and the new row written;
    a row costs every head a score over the whole row and a context over
    its latent part."""
    layers = layer_kinds(config).count('A')
    heads, rows = config['num_attention_heads'], context + 1
    return {'bytes': layers * batch * latent_row(config) * BYTES
            * (rows + 1),
            'flops': layers * batch * heads * 2 * rows
            * (latent_row(config) + config['kv_lora_rank'])}


def expert_bytes(config):
    """One routed expert's three matrices."""
    return (3 * config['hidden_size'] * config['moe_intermediate_size']
            * BYTES)


def experts_held(config):
    lo, hi = config['experts_held']
    return hi - lo


def group_size(config):
    return config['published']['num_experts'] // config['n_group']


def expected_group_rows(config, tokens):
    """Tokens of ``tokens`` that keep the held group under uniform
    routing: ``topk_group`` of ``n_group`` groups a token."""
    return tokens * config['topk_group'] / config['n_group']


def expected_distinct_held(config, tokens):
    """Distinct HELD experts that ``tokens`` tokens hit in one layer
    under uniform group-limited routing, the held experts one whole
    group: a token keeps the group with probability ``topk_group /
    n_group`` and then spreads its ``k`` picks over the ``topk_group``
    kept groups' experts, so it picks a given held expert with
    probability ``k / n_experts`` — as ungrouped routing would."""
    e = config['published']['num_experts']
    k = config['num_experts_per_tok']
    return experts_held(config) * (1.0 - (1.0 - k / e) ** tokens)


def cache_gib(caches):
    """``{'latent_gib', 'state_gib'}``: the bytes of the buffers that
    ``make_decode_caches`` built, the latent rows of the layers whose
    cache grows and state + window of the recurrent ones."""
    out = {'latent_gib': 0.0, 'state_gib': 0.0}
    for cache in caches:
        if hasattr(cache, 'state'):
            out['state_gib'] += (cache.state.nbytes
                                 + cache.conv.nbytes) / 2.0 ** 30
        else:
            out['latent_gib'] += cache.rows.nbytes / 2.0 ** 30
    return out
