"""Operations and bytes the latent-attention / sparse-expert decode step
needs, from the configuration's shapes alone (``flops.py``'s rules: a
multiply-add is two operations, only needed work is counted). Kept with
the benchmark so that no PR that claims a gain can change the yardstick.
"""

BYTES = 2       # bfloat16 weights and cache


def mla_decode_step(config, batch, context):
    """The latent decode kernel in one token step over ``batch``
    sessions holding ``context`` rows each, all layers: every valid row
    ``[c_kv ; k_rope]`` read once for all heads and the new row written
    (the zero columns the cache pads a row with are no needed bytes);
    per head and row a score over the whole row and a context over its
    latent part."""
    rank, rope = config['kv_lora_rank'], config['qk_rope_head_dim']
    layers, heads = config['num_hidden_layers'], config['num_attention_heads']
    rows = context + 1
    return {'bytes': layers * batch * (rank + rope) * BYTES * (rows + 1),
            'flops': layers * batch * 2 * heads * (2 * rank + rope) * rows}


def expert_bytes(config):
    """One routed expert's three matrices."""
    return (3 * config['hidden_size'] * config['moe_intermediate_size']
            * BYTES)


def expert_layers(config):
    return config['num_hidden_layers'] - config['first_k_dense_replace']


def expected_distinct_experts(config, tokens):
    """Distinct experts ``tokens`` uniform top-k picks hit in one layer:
    ``E (1 - (1 - k/E)^tokens)``."""
    e, k = config['n_routed_experts'], config['num_experts_per_tok']
    return e * (1.0 - (1.0 - k / e) ** tokens)
