"""Operations and bytes the algorithm needs, from shapes alone. Kept with
the benchmark so that no PR that claims a gain can change the yardstick.

A multiply-add is two operations. Recomputed work (remat's second
forward, the backward kernels' rebuilt scores) is not needed work and is
not counted, so every share computed from these is conservative.
"""


def causal_pairs(seq_len, window=None, start=0, rows=None):
    """Query-key pairs a causal (optionally windowed) attention scores
    for query rows ``start .. start + rows`` of a ``seq_len`` sequence:
    row i sees keys ``max(0, i - window + 1) .. i``."""
    end = seq_len if rows is None else start + rows

    def triangle(n):                 # rows 0 .. n-1 with no window
        return n * (n + 1) // 2

    if window is None:
        return triangle(end) - triangle(start)
    ramp_end = min(end, max(start, window - 1))   # rows short of a window
    return (triangle(ramp_end) - triangle(start)
            + (end - ramp_end) * window)


def layer_matmul_flops_per_token(sizes):
    """Forward operations per token in one block's dense layers."""
    d, h = sizes['dim'], sizes['num_heads']
    kv = (sizes['attn_kwargs'].get('num_kv_heads') or h) * (d // h)
    return 2 * (2 * d * d + 2 * d * kv + 2 * d * sizes['mlp_ratio'] * d)


def attention_flops(sizes, pairs):
    """Forward operations of one layer's attention over ``pairs`` pairs:
    scores and context, 2 * head_dim each per head."""
    return 4 * sizes['dim'] * pairs


def forward_flops_per_token(sizes, seq_len):
    """Model forward operations per token at sequence length
    ``seq_len``: dense layers, attention at its causal (windowed) pair
    count, and the head."""
    window = sizes['attn_kwargs'].get('window')
    attn = attention_flops(sizes, causal_pairs(seq_len, window)) / seq_len
    per_layer = layer_matmul_flops_per_token(sizes) + attn
    return (sizes['n_layers'] * per_layer
            + 2 * sizes['dim'] * sizes['vocab_size'])


def train_flops_per_token(sizes, seq_len):
    """Forward plus backward (twice the forward); remat not counted."""
    return 3 * forward_flops_per_token(sizes, seq_len)


def flash_train_step(sizes, seq_len, chips=1):
    """Needed operations and HBM bytes of the flash forward and backward
    kernels in one train step ON THE BUSIEST CHIP of a contiguous
    sequence split (the last shard, whose rows see every key): forward
    ``4 d`` a pair and head, backward ``8 d`` (dQ, dK, dV and dP; the
    rebuilt scores are recompute). Bytes: q, k, v, o read or written
    once a pass in bfloat16, gradients likewise."""
    d, h = sizes['dim'], sizes['num_heads']
    kv = (sizes['attn_kwargs'].get('num_kv_heads') or h) * (d // h)
    window = sizes['attn_kwargs'].get('window')
    rows = seq_len // chips
    pairs = causal_pairs(seq_len, window, start=seq_len - rows, rows=rows)
    keys = seq_len if window is None else min(seq_len, rows + window)
    layers = sizes['n_layers']
    fwd_bytes = 2 * (2 * rows * d + 2 * keys * kv)
    bwd_bytes = 2 * (4 * rows * d + 4 * keys * kv)
    return {'flops': layers * 12 * d * pairs,
            'bytes': layers * (fwd_bytes + bwd_bytes)}


def decode_step(sizes, batch, context, t_max=None):
    """Needed HBM bytes and operations of the fused append+attend
    kernels in one token step over ``batch`` sessions holding ``context``
    valid rows each: the valid K and V rows read (the window's, where
    there is one) and the new rows written, in bfloat16."""
    d, h = sizes['dim'], sizes['num_heads']
    kv = (sizes['attn_kwargs'].get('num_kv_heads') or h) * (d // h)
    window = sizes['attn_kwargs'].get('window')
    rows = context + 1 if window is None else min(context + 1, window)
    layers = sizes['n_layers']
    return {'bytes': layers * batch * 2 * 2 * kv * (rows + 1),
            'flops': layers * batch * 4 * d * rows}
