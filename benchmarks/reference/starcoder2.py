"""StarCoder2's attention particulars for the plain reference
(``common.py``): rotary embedding on q and k at theta = rope_theta,
grouped-query heads, a sliding window, biases on every projection, and
no additive score bias."""

from benchmarks.reference import common


def rotate(x, positions, sizes):
    return common.rope_half(x, positions,
                            sizes['attn_kwargs']['rope_base'])


def score_bias(dist, sizes):
    return None
