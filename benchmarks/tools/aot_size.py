"""Compile a cell's programs at real size for a described v5e (no chip
attached) and print ``memory_analysis()``: this is what fixes each
``reduced`` depth and the decode batch. Rehearsal only: nothing runs, and
nothing it prints is a device metric.

    JAX_PLATFORMS=cpu python benchmarks/tools/aot_size.py train starcoder2-3b --layers 5 --chips 1
    JAX_PLATFORMS=cpu python benchmarks/tools/aot_size.py decode mpt-7b-serve --layers 8 --sessions 4
"""

import argparse
import os
import sys
import time

os.environ.setdefault('TPU_LOG_DIR', 'disabled')
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from benchmarks import loader, system, weights  # noqa: E402

GIB = 2.0 ** 30


def report(name, compiled, t0):
    m = compiled.memory_analysis()
    text = compiled.as_text()
    print(f'{name}: args {m.argument_size_in_bytes / GIB:.2f} GiB, out '
          f'{m.output_size_in_bytes / GIB:.2f}, alias '
          f'{m.alias_size_in_bytes / GIB:.2f}, temp '
          f'{m.temp_size_in_bytes / GIB:.2f} GiB; '
          f'tpu_custom_call x{text.count("tpu_custom_call")}, all-gather '
          f'x{text.count("all-gather-start") or text.count("all-gather(")}'
          f', all-reduce x{text.count("all-reduce-start") or text.count("all-reduce(")}'
          f'; peak {getattr(m, "peak_memory_in_bytes", 0) / GIB:.2f} GiB'
          f'; compile {time.time() - t0:.0f} s', flush=True)
    return text


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('kind', choices=['train', 'decode'])
    ap.add_argument('config')
    ap.add_argument('--layers', type=int, required=True)
    ap.add_argument('--chips', type=int, default=1)
    ap.add_argument('--seq', type=int, default=16384)
    ap.add_argument('--sessions', type=int, default=4)
    ap.add_argument('--t-max', type=int, default=16384)
    ap.add_argument('--chunk', type=int, default=2048)
    ap.add_argument('--loss-chunk', type=int, default=4096)
    ap.add_argument('--dump', default=None)
    args = ap.parse_args()

    # The program asks jax.default_backend() to choose compiled kernels
    # over interpreted ones; here, and only here, answer for the chip.
    jax.default_backend = lambda: 'tpu'
    jax.config.update('jax_enable_compilation_cache', False)
    topo = topologies.get_topology_desc(platform='tpu',
                                        topology_name='v5e:2x2')
    devices = topo.devices[:args.chips]
    config = loader.read_json(loader.HERE, 'configs', f'{args.config}.json')
    depth_key = config['program']['n_layers']
    config[depth_key] = args.layers
    model = system.build_lm(config)

    from distributed_dot_product_tpu.parallel.mesh import seq_mesh
    mesh = seq_mesh(args.chips, devices=devices)
    rep = NamedSharding(mesh, P())

    def struct(tree, dtype=None):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, dtype or x.dtype, sharding=rep), tree)

    p_shapes = {'params': {}}
    for path, (shape, _) in weights.shapes(config).items():
        node = p_shapes['params']
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = jax.ShapeDtypeStruct(shape, jnp.float32)
    t0 = time.time()
    if args.kind == 'train':
        from distributed_dot_product_tpu.train import make_lm_train_step
        from benchmarks.drivers.train import make_optimizer
        opt = make_optimizer(loader.read_json(
            loader.HERE, 'traffic', 'train-16k.json')['optimizer'])
        params = struct(p_shapes)
        opt_state = struct(jax.eval_shape(opt.init, p_shapes))
        step = make_lm_train_step(model, opt, mesh, guard=False,
                                  loss_chunk=args.loss_chunk)
        tok = jax.ShapeDtypeStruct(
            (1, args.seq), jnp.int32,
            sharding=NamedSharding(mesh, P(None, 'seq')))
        text = report(f'train step L={args.layers} T={args.seq} chunk={args.loss_chunk} '
                      f'chips={args.chips}',
                      step.lower(params, opt_state, (tok, tok)).compile(),
                      t0)
    else:
        params = struct(p_shapes, jnp.bfloat16)
        caches = struct(jax.eval_shape(
            lambda: model.make_decode_caches(args.sessions, args.t_max)))
        tok = jax.ShapeDtypeStruct((args.sessions, args.chunk), jnp.int32,
                                   sharding=rep)
        from benchmarks.drivers.decode import make_programs
        prefill, step = make_programs(model)
        try:
            report(f'prefill chunk={args.chunk}',
                   prefill.lower(params, tok, caches).compile(), t0)
        except Exception as e:   # report and go on to the step
            print('prefill does not compile:', str(e)[:300], flush=True)
        t0 = time.time()
        one = jax.ShapeDtypeStruct((args.sessions, 1), jnp.int32,
                                   sharding=rep)
        text = report(f'decode step B={args.sessions} t_max={args.t_max}',
                      step.lower(params, one, caches).compile(), t0)
    if args.dump:
        with open(args.dump, 'w') as f:
            f.write(text)


if __name__ == '__main__':
    main()
