"""The two plain references against the system at a tiny size on the
CPU, the weight tree against the program's, and the control: the same
comparison has to fail for a run that scores in int8."""

import jax
import jax.numpy as jnp
import pytest

from benchmarks import loader, system, weights
from benchmarks.drivers import train as train_driver
from benchmarks.harness import Compare

CONTROL = {'qk_quant': 'int8'}


@pytest.mark.parametrize('name', ['starcoder2-3b', 'mpt-7b', 'mpt-7b-serve'])
def test_weight_tree_is_the_one_the_program_takes(name):
    config = loader.read_json(loader.HERE, 'configs', f'{name}.json')
    model = system.build_lm(config)
    want = jax.eval_shape(model.init, jax.random.key(0),
                          jnp.zeros((1, 16), jnp.int32))
    want = {tuple(k.key for k in path[1:]): leaf.shape
            for path, leaf in jax.tree_util.tree_leaves_with_path(want)}
    assert {k: v[0] for k, v in weights.shapes(config).items()} == want


def test_weights_follow_the_seed_and_nothing_else(tiny_root):
    config = loader.Cell('tiny-mpt.train', root=tiny_root).config
    a = weights.make(config, 3_000_000_019)
    b = weights.make(config, 3_000_000_019)
    c = weights.make(config, 3_000_000_020)
    leaves = jax.tree.leaves
    assert all(bool(jnp.all(x == y)) for x, y in zip(leaves(a), leaves(b)))
    assert any(bool(jnp.any(x != y)) for x, y in zip(leaves(a), leaves(c)))
    rounded = weights.make(config, 7, jnp.bfloat16, upcast=True)
    served = weights.make(config, 7, jnp.bfloat16)
    assert all(x.dtype == jnp.float32 and bool(jnp.all(
        x == y.astype(jnp.float32)))
        for x, y in zip(leaves(rounded), leaves(served)))


def first_steps(cell, seed, overrides=None):
    t = cell.traffic
    trainer = train_driver.Trainer(cell, seed, attn_overrides=overrides)
    want = train_driver.reference_steps(cell, seed, trainer.batches,
                                        t['optimizer'], t['check_steps'])
    trainer.init_state()
    trainer.compile()
    got = trainer.first_steps(t['check_steps'])
    compare = Compare()
    train_driver.compare_first_steps(compare, got, want, cell.limits)
    return compare


@pytest.mark.parametrize('workload', [
    'tiny-starcoder2.train', 'tiny-mpt.train', 'tiny-mpt.train4',
    'tiny-starcoder2-f32.train', 'tiny-mpt-f32.train'])
def test_training_agrees_with_the_reference(tiny_root, workload):
    compare = first_steps(loader.Cell(workload, root=tiny_root), seed=11)
    assert compare.correct, compare.rows


@pytest.mark.parametrize('workload', ['tiny-starcoder2-f32.train',
                                      'tiny-mpt-f32.train'])
def test_int8_scores_fail_the_tolerance(tiny_root, workload):
    """At float32 compute the sound run sits at rounding error, so the
    int8-scored run has to leave the limits it passes (the chip's
    bfloat16 cells have their own readings in PERF.md)."""
    compare = first_steps(loader.Cell(workload, root=tiny_root), seed=11,
                          overrides=CONTROL)
    assert not compare.correct
    failed = {r['compared'] for r in compare.rows if not r['ok']}
    assert 'attn_grad_rel_diff' in failed, compare.rows
