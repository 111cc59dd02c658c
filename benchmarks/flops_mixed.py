"""Operations and bytes that the decode step of a stack of window and
full attention layers needs (the ``cohere2_moe`` configuration), from
the configuration's shapes alone (``flops.py``'s rules: a multiply-add
is two operations, only needed work is counted). Kept with the
benchmark so that no PR that claims a gain can change the yardstick.
"""

BYTES = 2       # bfloat16 weights and cache


def layer_counts(config):
    """``(window layers, full layers)`` of the depth held."""
    kinds = config['layer_types'][:config['num_hidden_layers']]
    ring = sum(kind == 'sliding_attention' for kind in kinds)
    return ring, len(kinds) - ring


def _kernel_step(config, layers, batch, rows):
    """``layers`` attention layers in one token step over ``batch``
    sessions of which each attends ``rows`` cached rows: every one of
    those K and V rows read once for its KV head's whole query group,
    and the new row written; per query head and row a score and a
    context over the head's width."""
    kv, heads = config['num_key_value_heads'], config['num_attention_heads']
    d = config['head_dim']
    return {'bytes': layers * batch * kv * 2 * d * BYTES * (rows + 1),
            'flops': layers * batch * heads * 4 * d * rows}


def full_decode_step(config, batch, context):
    """The full-attention layers' decode kernel: the new row attends
    itself and all ``context`` rows before it."""
    return _kernel_step(config, layer_counts(config)[1], batch,
                        context + 1)


def ring_decode_step(config, batch, context):
    """The window layers' decode kernel (its ring mode): the new row
    attends itself and the rows before it that the window holds."""
    return _kernel_step(config, layer_counts(config)[0], batch,
                        min(context + 1, config['sliding_window']))


def expert_bytes(config):
    """One routed expert's three matrices."""
    return 3 * config['hidden_size'] * config['intermediate_size'] * BYTES


def experts_held(config):
    lo, hi = config['experts_held']
    return hi - lo


def expected_distinct_held(config, tokens):
    """Distinct HELD experts that ``tokens`` uniform top-k picks over
    the router's whole width hit in one layer: ``held (1 - (1 -
    k/E)^tokens)``."""
    e, k = config['published']['num_experts'], config['num_experts_per_tok']
    return experts_held(config) * (1.0 - (1.0 - k / e) ** tokens)


def cache_gib(caches):
    """``{'full_gib', 'ring_gib'}``: the bytes of the K and V buffers
    that the program built, by whether a cache recycles its rows (it
    then has a ``capacity``)."""
    out = {'full_gib': 0.0, 'ring_gib': 0.0}
    for cache in caches:
        kind = 'ring_gib' if hasattr(cache, 'capacity') else 'full_gib'
        out[kind] += (cache.k.nbytes + cache.v.nbytes) / 2.0 ** 30
    return out
