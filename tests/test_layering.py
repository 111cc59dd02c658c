# -*- coding: utf-8 -*-
"""
The package's module graph: the kernels, the model, the mesh helpers and
the train step (``ops/``, ``models/``, ``parallel/``, ``train.py``) sit
BELOW the tooling (``obs/``, ``analysis/``, ``serve/``) and import none
of it, at module level or inside a function. What they need of it — the
device-scope names, the retrace guard, the trace sinks — are leaves
under ``utils/``.
"""

import ast
import os

import pytest

PKG = 'distributed_dot_product_tpu'
ROOT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), PKG)
UPPER = ('obs', 'analysis', 'serve')


def _lower_files():
    out = ['train.py']
    for sub in ('ops', 'models', 'parallel'):
        out += sorted(os.path.join(sub, f)
                      for f in os.listdir(os.path.join(ROOT, sub))
                      if f.endswith('.py'))
    return out


def _imported_modules(rel, root=ROOT):
    """``(lineno, absolute dotted name)`` of everything ``rel`` imports,
    at any depth of its AST: ``import a.b``, ``from a import b`` (both
    ``a`` and ``a.b``: ``b`` may be a module), relative forms resolved
    against the file's own package, and literal ``import_module`` /
    ``__import__`` arguments."""
    with open(os.path.join(root, rel), encoding='utf-8') as f:
        tree = ast.parse(f.read(), rel)
    here = [PKG] + rel.split(os.sep)[:-1]
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for alias in node.names:
                yield node.lineno, alias.name
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ''
            if node.level:
                up = here[:len(here) - node.level + 1]
                base = '.'.join(up + ([base] if base else []))
            yield node.lineno, base
            for alias in node.names:
                yield node.lineno, f'{base}.{alias.name}'
        elif (isinstance(node, ast.Call) and node.args
              and isinstance(node.args[0], ast.Constant)
              and isinstance(node.args[0].value, str)
              and getattr(node.func, 'attr', getattr(node.func, 'id', ''))
              in ('import_module', '__import__')):
            yield node.lineno, node.args[0].value


def _is_upper(name):
    return any(name == f'{PKG}.{up}' or name.startswith(f'{PKG}.{up}.')
               for up in UPPER)


@pytest.mark.parametrize('rel', _lower_files())
def test_lower_layer_imports_no_tooling(rel):
    bad = sorted({(line, name) for line, name in _imported_modules(rel)
                  if _is_upper(name)})
    assert not bad, (
        f'{PKG}/{rel} imports the tooling above it: '
        + ', '.join(f'line {line}: {name}' for line, name in bad))


def _ops_files():
    return sorted(os.path.join('ops', f)
                  for f in os.listdir(os.path.join(ROOT, 'ops'))
                  if f.endswith('.py'))


@pytest.mark.parametrize('rel', _ops_files())
def test_ops_import_no_utils_tracing(rel):
    """``utils/tracing.py`` reaches ``obs/`` (its event log); since the
    ``@measure`` decorator went, no kernel file needs it: build time is
    the leaf ``utils/build_ledger.py``'s."""
    bad = [(line, name) for line, name in _imported_modules(rel)
           if name.startswith(f'{PKG}.utils.tracing')]
    assert not bad, bad


def test_the_build_ledger_is_a_leaf():
    """``utils/build_ledger.py`` imports JAX and nothing of the package,
    so ``ops/`` may use it."""
    mine = [name for _, name in _imported_modules(
        os.path.join('utils', 'build_ledger.py')) if name.startswith(PKG)]
    assert not mine, mine


def test_obs_and_serve_import_nothing_of_the_lint():
    """The lint's example programs import the engine and the spans, not
    the reverse: ``obs/`` and ``serve/`` name no ``analysis`` module."""
    bad = [(rel, line, name)
           for sub in ('obs', 'serve')
           for rel in sorted(os.path.join(sub, f)
                             for f in os.listdir(os.path.join(ROOT, sub))
                             if f.endswith('.py'))
           for line, name in _imported_modules(rel)
           if name.startswith(f'{PKG}.analysis')]
    assert not bad, bad


def test_the_walk_sees_lazy_relative_and_dynamic_imports(tmp_path):
    """The guard guards: a lazy absolute import, a relative one and an
    ``import_module`` string are each found."""
    sub = tmp_path / 'models'
    sub.mkdir()
    (sub / 'x.py').write_text(
        'def f():\n'
        '    from distributed_dot_product_tpu.obs.spans import span\n'
        '    from .. import serve\n'
        '    from ..analysis.registry import TraceSpec\n'
        '    import importlib\n'
        '    importlib.import_module("distributed_dot_product_tpu.obs")\n'
        '    from distributed_dot_product_tpu.utils.scopes import x\n')
    found = {name for _, name in
             _imported_modules(os.path.join('models', 'x.py'),
                               root=str(tmp_path))
             if _is_upper(name)}
    assert found == {
        f'{PKG}.obs', f'{PKG}.obs.spans', f'{PKG}.obs.spans.span',
        f'{PKG}.serve', f'{PKG}.analysis.registry',
        f'{PKG}.analysis.registry.TraceSpec'}


def test_device_scopes_are_the_leafs_table():
    """``obs.spans`` re-exports the leaf's two names, not copies (the
    benchmark's readers import them from there), and an unknown scope
    is still refused."""
    from distributed_dot_product_tpu.obs import spans
    from distributed_dot_product_tpu.utils import scopes
    assert spans.DEVICE_SCOPES is scopes.DEVICE_SCOPES
    assert spans.device_scope is scopes.device_scope
    with pytest.raises(ValueError, match='unknown device scope'):
        scopes.device_scope('no.such')
    with scopes.device_scope('ops.flash_fwd'):
        pass


def test_the_latent_cache_lives_below_the_latent_mixer():
    """``models/decode.py`` holds every cache type and the functions
    that move sessions and states between them (``insert_session``,
    ``snapshot_states`` / ``restore_states``) and imports no mixer:
    ``LatentCache`` is defined there, beside ``StateCache``, and
    ``models/latent.py`` re-exports the same objects (the Xing4 cell's
    driver imports ``insert_session`` from there)."""
    from distributed_dot_product_tpu.models import decode, latent
    mixers = {f'{PKG}.models.{m}' for m in (
        'latent', 'delta', 'ssm', 'lightning', 'shortconv', 'sparse', 'moe',
        'transformer', 'attention')}
    assert not {name for _, name in _imported_modules(
        'models/decode.py') if any(
            name == m or name.startswith(m + '.') for m in mixers)}
    assert latent.LatentCache is decode.LatentCache
    assert latent.insert_session is decode.insert_session
    assert 'LatentCache' in decode.__all__


def test_the_convolution_mixer_is_a_leaf_beside_the_other_mixers():
    """``models/shortconv.py`` imports the cache types, the dense layer
    and the two leaves (scopes, trace sinks) and nothing else of the
    package — no other mixer, no kernel, nothing of ``analysis/``,
    ``obs/`` or ``serve/`` —, the stack knows it through ``RECURRENT``
    alone, and ``PackedCache`` lives beside the other caches."""
    from distributed_dot_product_tpu.models import decode, transformer
    from distributed_dot_product_tpu.models.shortconv import ShortConvMixer
    mine = {name for _, name in _imported_modules('models/shortconv.py')
            if name.startswith(PKG)}
    allowed = {f'{PKG}.models.decode', f'{PKG}.models.dense',
               f'{PKG}.utils.scopes', f'{PKG}.utils.trace_sinks'}
    assert mine and all(any(name == m or name.startswith(m + '.')
                            for m in allowed) for name in mine)
    assert transformer.RECURRENT['conv'] is ShortConvMixer
    assert {'PackedCache', 'init_packed_cache', 'packed_append',
            'packed_views'} <= set(decode.__all__)
