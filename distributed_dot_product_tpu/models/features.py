# -*- coding: utf-8 -*-
"""
Feature × softmax-path support matrix — the single source of truth.

Every attention knob's support across the four ``softmax_impl`` paths of
:class:`~distributed_dot_product_tpu.models.attention.DistributedDotProductAttn`
lives in this one declarative table. Three consumers keep it honest:

- ``DistributedDotProductAttn.setup()`` raises from it (one uniform
  message instead of scattered per-knob raise sites);
- ``README.md``'s support table is generated from it
  (``python -m distributed_dot_product_tpu.models.features``);
- ``tests/test_feature_matrix.py`` asserts every cell against actual
  behavior — a 'yes' cell must run, a 'no' cell must raise — and that the
  README table is in sync.

The reference has ONE path and two knobs (``offset``, ``distributed``,
reference module.py:23-26), so it needs no such table; this framework's
4 paths × 12 knobs do.

Vocabulary: ``True`` = supported natively; ``False`` = raises; a string =
supported with a caveat (shown in the README table; treated as supported
by validation).
"""

IMPLS = ('full', 'online', 'flash', 'ulysses')

# knob -> {impl: True | False | 'caveat string'}
FEATURE_MATRIX = {
    'attn_mask': {
        'full': True,
        'online': 'O(T²/N) input',
        'flash': 'O(T²/N) input; blockwise skip/redirect',
        'ulysses': 'gathered to O(T²) per device',
    },
    'causal': {
        'full': 'densified into the mask',
        'online': 'native (block + whole-fold skip)',
        'flash': 'native (block skip)',
        'ulysses': 'native (block skip)',
    },
    'window': {
        'full': 'densified into the mask',
        'online': 'native (whole-fold skip)',
        'flash': 'native (banded grid, O(T·window))',
        'ulysses': 'native (banded grid)',
    },
    'segment_ids': {
        'full': 'densified into the mask',
        'online': 'native O(T/N) vectors, rotate with K/V',
        'flash': 'native O(T) vectors',
        'ulysses': 'native O(T) vectors',
    },
    'num_kv_heads': {
        'full': 'heads repeated (parity path)',
        'online': 'native grouped kernels',
        'flash': 'native grouped kernels',
        'ulysses': 'native; needs num_kv_heads % N == 0',
    },
    'dropout_rate': {
        'full': False,
        'online': 'in-kernel hash mask',
        'flash': 'in-kernel hash mask',
        'ulysses': 'in-kernel hash mask',
    },
    'alibi_slopes': {
        'full': False,
        'online': 'in-kernel, global distances',
        'flash': 'in-kernel, global distances',
        'ulysses': 'in-kernel, global distances',
    },
    'qk_quant': {
        'full': False,
        'online': 'int8 MXU scoring (per-fold kernels)',
        'flash': 'int8 MXU scoring',
        'ulysses': 'int8 MXU scoring (local flash kernel)',
    },
    'use_rope': {
        'full': 'shard-global rotation',
        'online': 'shard-global rotation (zigzag-aware)',
        'flash': 'shard-global rotation',
        'ulysses': 'shard-global rotation',
    },
    'ring_layout=zigzag': {
        'full': False,
        'online': 'causal critical-path balance',
        'flash': False,
        'ulysses': False,
    },
    'flash_softmax_mode=bounded': {
        'full': False,
        'online': False,
        'flash': 'forward-only; not timed on this chip',
        'ulysses': 'forward-only; not timed on this chip',
    },
    'offset': {
        'full': 'chunked-gather knob (reference semantics)',
        'online': 'n/a (ring rotation)',
        'flash': 'n/a (one tiled gather)',
        'ulysses': 'n/a (all-to-all)',
    },
}

# Knob-interaction rules that are NOT per-path (kept next to the matrix so
# the README can list them; enforced by the module's setup()).
INTERACTION_RULES = (
    ('window', 'requires causal=True (lookback cap)'),
    ('alibi_slopes', 'requires causal=True (relative-position bias)'),
    ('ring_layout=zigzag',
     'requires causal=True; a dense attn_mask needs its ROW axis '
     'zigzag-permuted like the inputs (columns stay global)'),
    ('dropout_rate',
     "needs rngs={'dropout': key} at apply() or an explicit "
     'dropout_seed'),
    ('use_rope', 'requires an even head dim'),
)


def supports(knob, impl):
    """True/caveat-string when ``knob`` works under ``softmax_impl=impl``,
    False when the module raises."""
    return FEATURE_MATRIX[knob][impl]


def check(knob, impl):
    """Raise the uniform unsupported-knob error when the matrix says no."""
    if not FEATURE_MATRIX[knob][impl]:
        ok = [i for i in IMPLS if FEATURE_MATRIX[knob][i]]
        raise ValueError(
            f"{knob} is not supported with softmax_impl={impl!r}; "
            f"supported paths: {', '.join(ok) if ok else 'none'} "
            f'(see the feature matrix in README.md / models/features.py)')


def feature_table_markdown():
    """The README support table, generated — never hand-edited."""
    head = ('| knob \\ `softmax_impl` | ' + ' | '.join(
        f'`{i}`' for i in IMPLS) + ' |')
    sep = '|' + '---|' * (len(IMPLS) + 1)
    rows = []
    for knob, cells in FEATURE_MATRIX.items():
        def cell(value):
            if value is True:
                return 'yes'
            if value is False:
                return '—'
            return f'yes ({value})'
        rows.append('| `' + knob + '` | '
                    + ' | '.join(cell(cells[i]) for i in IMPLS) + ' |')
    rules = ['', 'Cross-knob rules (path-independent):', ''] + [
        f'- `{knob}`: {rule}' for knob, rule in INTERACTION_RULES]
    return '\n'.join([head, sep] + rows + rules)


if __name__ == '__main__':
    print(feature_table_markdown())
