"""``flops.py`` against values worked by hand from the published sizes."""

import pytest

from benchmarks import flops, loader, weights


def sizes(name):
    return weights.model_sizes(
        loader.read_json(loader.HERE, 'configs', f'{name}.json'))


@pytest.mark.parametrize('args, want', [
    ((16,), 136),                       # 1 + 2 + ... + 16
    ((16, 4), 1 + 2 + 3 + 13 * 4),      # ramp, then a full window a row
    ((16, 4, 8, 8), 8 * 4),             # the last shard of two
    ((16, None, 12, 4), 13 + 14 + 15 + 16),
    ((16384, 4096), 4095 * 4096 // 2 + (16384 - 4095) * 4096),
])
def test_causal_pairs(args, want):
    assert flops.causal_pairs(*args) == want


def test_starcoder2_hand_worked():
    s = sizes('starcoder2-3b')
    d, kv, hidden, vocab, layers = 3072, 2 * 128, 12288, 49152, 5
    assert s['n_layers'] == layers
    # q and o are d x d, k and v d x 256 (2 KV heads), the MLP 2 x d x 4d
    dense = 2 * (2 * d * d + 2 * d * kv + 2 * d * hidden)
    assert flops.layer_matmul_flops_per_token(s) == dense
    t = 16384
    pairs = 4095 * 4096 // 2 + (t - 4095) * 4096      # window band
    attn = 4 * d * pairs / t      # 24 heads x 128 = d, QK^T and PV
    fwd = layers * (dense + attn) + 2 * d * vocab
    assert flops.forward_flops_per_token(s, t) == pytest.approx(fwd)
    assert flops.train_flops_per_token(s, t) == pytest.approx(3 * fwd)
    flash = flops.flash_train_step(s, t)
    assert flash['flops'] == layers * 12 * d * pairs
    # q, o rows of d and k, v rows of 256, bf16, forward once, backward
    # reads and writes twice as many
    assert flash['bytes'] == layers * 3 * 2 * (2 * t * d + 2 * t * kv)


def test_mpt_hand_worked():
    s = sizes('mpt-7b')
    d, vocab, layers, t = 4096, 50432, 2, 16384
    dense = 2 * (4 * d * d + 2 * d * 4 * d)          # MHA: kv width = d
    assert flops.layer_matmul_flops_per_token(s) == dense
    pairs = t * (t + 1) // 2                          # causal half
    fwd = layers * (dense + 4 * d * pairs / t) + 2 * d * vocab
    assert flops.forward_flops_per_token(s, t) == pytest.approx(fwd)
    # four chips, contiguous split: the last holds rows 12288.. and sees
    # every key
    last = flops.flash_train_step(s, 4 * t, chips=4)
    rows = sum(i + 1 for i in range(3 * t, 4 * t))
    assert last['flops'] == layers * 12 * d * rows


def test_decode_bytes_hand_worked():
    s = sizes('mpt-7b-serve')
    step = flops.decode_step(s, batch=2, context=12288 + 128)
    # 8 layers x 2 sessions x (K and V) x 2 bytes x 4096 wide x
    # (12417 rows read + 1 written)
    assert step['bytes'] == 8 * 2 * 2 * 2 * 4096 * (12417 + 1)
    sc = sizes('starcoder2-3b')
    windowed = flops.decode_step(sc, batch=8, context=12288)
    assert windowed['bytes'] == 5 * 8 * 2 * 2 * 256 * (4096 + 1)


def test_alibi_slopes_are_mpts():
    slopes = sizes('mpt-7b')['attn_kwargs']['alibi_slopes']
    assert len(slopes) == 32
    assert slopes[0] == pytest.approx(2 ** -0.25)
    assert slopes[-1] == pytest.approx(2 ** -8)
