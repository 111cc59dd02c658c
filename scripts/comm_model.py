# -*- coding: utf-8 -*-
"""
Analytic per-step ICI communication model for the attention paths.

Multi-chip performance cannot be *measured* in this environment (one real
chip), so this is the checkable substitute: closed-form per-device ICI
byte counts and collective schedules for the three distributed attention
strategies as f(N, B, H, H_kv, T, d), cross-validated against the
collective ops XLA actually compiles on a virtual mesh
(``--check``, also run by tests/test_comm_model.py). ``--table`` prints
the model as a markdown table.

Model conventions
-----------------
- Bytes are per device per train step (fwd+bwd), counting only data that
  crosses the ICI: an all-gather of a T-sharded array of S global bytes
  delivers S·(N−1)/N to each device (its own shard is local); a
  psum-scatter (the all-gather's transpose) sends the same; one ppermute
  hop moves the full carried buffer per device.
- ``d`` is the per-head feature dim; activations are ``itemsize`` bytes
  (2 for bf16). The ring backward's rotating dk/dv partials are fp32 by
  design (ring_attention.py ``grad_dtype``) — 4 bytes regardless.
- The projections/loss/optimizer are identical across paths (parameter
  psum) and excluded: this model isolates what distinguishes the paths.

Path schedules (fwd + bwd of ONE attention call):

allgather-flash (module softmax_impl='flash', models/attention.py):
    fwd: all_gather(queries) + all_gather(values) — H_kv heads each.
    bwd: their transposes: psum_scatter(d_queries) + psum_scatter(d_values).
ring (softmax_impl='online', models/ring_attention.py):
    fwd: (N−1) scan steps each ppermute-ing the (k, v) shard pair.
    bwd: (N−1) steps rotating (k, v, dk, dv) + one final (dk, dv) hop.
ulysses (softmax_impl='ulysses', models/ulysses_attention.py):
    fwd: all_to_all for q (H heads), k, v (H_kv each) + the output
         gather (H heads). bwd: the mirrored four all_to_alls.
"""

import argparse
import math
import re


def _ag_bytes(global_bytes, n):
    """Per-device ICI bytes for one tiled all-gather (or its transpose,
    the psum-scatter) of a T-sharded array."""
    return global_bytes * (n - 1) / n


def comm_model(path, *, n, b=1, h=8, h_kv=None, t=16384, d=64, itemsize=2):
    """Collective schedule + per-device ICI bytes for one fwd+bwd
    attention call. Returns ``{'collectives': [(kind, count,
    bytes_per_device_per_op)], 'total_bytes': float}``."""
    h_kv = h_kv or h
    shard = t // n
    kv_global = b * h_kv * t * d * itemsize      # one of k/v, global
    kv_shard = b * h_kv * shard * d * itemsize   # one of k/v, per shard
    q_shard = b * h * shard * d * itemsize
    cols = []
    if path == 'allgather':
        # models/attention.py flash branch: q_full/v_full gathers (the
        # K-first module gathers its queries/values — H_kv heads under
        # GQA, which is where num_kv_heads cuts ICI bytes).
        cols.append(('all-gather', 2, _ag_bytes(kv_global, n)))
        cols.append(('reduce-scatter', 2, _ag_bytes(kv_global, n)))
    elif path == 'ring':
        # models/ring_attention.py: scan of (N−1) ppermute steps; the
        # backward rotates fp32 dk/dv partials with the k/v buffers and
        # delivers them home with one extra hop.
        cols.append(('collective-permute(fwd k,v)', n - 1, 2 * kv_shard))
        dkv = 2 * b * h_kv * shard * d * 4       # fp32 partials
        cols.append(('collective-permute(bwd k,v,dk,dv)', n - 1,
                     2 * kv_shard + dkv))
        cols.append(('collective-permute(bwd deliver)', 1, dkv))
    elif path == 'ulysses':
        # models/ulysses_attention.py: head↔time all_to_all per operand
        # + the output gather; backward mirrors all four.
        per = _ag_bytes(q_shard, n)        # all_to_all moves (N−1)/N of
        per_kv = _ag_bytes(kv_shard, n)    # the LOCAL shard per device
        cols.append(('all-to-all(fwd q,k,v)', 1, per + 2 * per_kv))
        cols.append(('all-to-all(fwd out)', 1, per))
        cols.append(('all-to-all(bwd dq,dk,dv)', 1, per + 2 * per_kv))
        cols.append(('all-to-all(bwd d_out)', 1, per))
    else:
        raise ValueError(f'unknown path {path!r}')
    total = sum(c * by for _, c, by in cols)
    return {'collectives': cols, 'total_bytes': total}


# --------------------------------------------------------------------------
# HLO cross-check: compile each path on a virtual mesh and count the
# collective ops XLA actually emitted, with their operand bytes.
# --------------------------------------------------------------------------

_DTYPE_BYTES = {'f32': 4, 'bf16': 2, 'f16': 2, 's32': 4, 'u32': 4,
                's8': 1, 'u8': 1, 'pred': 1, 'f64': 8, 's64': 8}

_COLL_RE = re.compile(
    r'%\S+\s*=\s*(\(?(?:(?:/\*[^*]*\*/)?\s*\w+\[[\d,]*\]'
    r'(?:\{[\d,:T()]*\})?,?\s*)+\)?)\s*'
    r'(all-gather|reduce-scatter|collective-permute|all-to-all)\(')


def _shape_bytes(shapes_str):
    total = 0
    for m in re.finditer(r'(\w+)\[([\d,]*)\]', shapes_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for piece in dims.split(','):
            if piece:
                n *= int(piece)
        total += n * _DTYPE_BYTES[dtype]
    return total


def hlo_collectives(compiled_text):
    """(kind, output_bytes) for every collective op in compiled HLO text.
    Loop-carried ops (the ring's scan) appear once each — the schedule
    validation below compares per-op shapes/counts, which is exactly
    what the flat text can certify; trip counts are structural (the scan
    length is N−1 by construction)."""
    out = []
    for m in _COLL_RE.finditer(compiled_text):
        out.append((m.group(2), _shape_bytes(m.group(1))))
    return out


def expected_hlo_ops(path, *, n, b=1, h=8, h_kv=None, t=256, d=16,
                     itemsize=4):
    """The op-level multiset the compiled HLO must contain: (kind,
    output_bytes) per collective OP (XLA emits one op per rotated buffer;
    loop ops appear once in the text). The module computes in f32 by
    default, hence itemsize=4 here."""
    h_kv = h_kv or h
    shard = t // n
    kv_shard = b * h_kv * shard * d * itemsize
    kv_global = b * h_kv * t * d * itemsize
    q_shard = b * h * shard * d * itemsize
    if path == 'allgather':
        # fwd: gather queries + values (output = full array); bwd: their
        # transposes (reduce-scatter output = the shard).
        return ([('all-gather', kv_global)] * 2
                + [('reduce-scatter', kv_global // n)] * 2)
    if path == 'ring':
        # fwd loop: k and v rotate as separate permutes; bwd loop: k, v,
        # dk, dv; after the loop: dk, dv delivery. All f32 here (module
        # math dtype), matching kv_shard with itemsize=4.
        return [('collective-permute', kv_shard)] * 8
    if path == 'ulysses':
        # 4 all_to_alls fwd+bwd mirrored: q-sized for q and the output,
        # kv-sized for k and v (equal when h_kv == h).
        return ([('all-to-all', q_shard)] * 4
                + [('all-to-all', kv_shard)] * 4)
    raise ValueError(path)


def check_against_hlo(n=8, b=1, h=8, h_kv=None, t=256, d=16):
    """Compile each path's fwd+bwd on an ``n``-device virtual mesh and
    reconcile the model's collective schedule against what XLA actually
    emitted: per-path, the multiset of (collective kind, op output bytes)
    must match ``expected_hlo_ops`` exactly. Returns
    path -> {'expected': [...], 'got': [...], 'match': bool}."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from distributed_dot_product_tpu.models.attention import (
        DistributedDotProductAttn,
    )
    from distributed_dot_product_tpu.parallel.mesh import seq_mesh

    h_kv = h_kv or h
    mesh = seq_mesh(n)
    dim = h * d
    results = {}
    for path, impl in (('allgather', 'flash'), ('ring', 'online'),
                       ('ulysses', 'ulysses')):
        model = DistributedDotProductAttn(
            key_dim=dim, num_heads=h,
            num_kv_heads=(h_kv if h_kv != h else None), causal=True,
            softmax_impl=impl)
        x = jnp.zeros((b, t, dim), jnp.float32)
        params = model.init(jax.random.key(0), x[:, :16], x[:, :16],
                            x[:, :16], None)

        def loss(p, k, q, v):
            spec = P(None, 'seq', None)
            fn = jax.shard_map(
                lambda p, k, q, v: model.apply(p, k, q, v, None),
                mesh=mesh, in_specs=(P(), spec, spec, spec),
                out_specs=spec, check_vma=False)
            return jnp.sum(fn(p, k, q, v).astype(jnp.float32) ** 2)

        compiled = jax.jit(jax.grad(loss)).lower(params, x, x, x).compile()
        got = sorted(hlo_collectives(compiled.as_text()))
        want = sorted(expected_hlo_ops(path, n=n, b=b, h=h, h_kv=h_kv,
                                       t=t, d=d))
        results[path] = {'expected': want, 'got': got,
                         'match': got == want}
    return results


def table_markdown(n=8, b=1, h=8, t=131072, d=96, itemsize=2):
    """The communication model as a markdown table."""
    lines = [
        '| path | collective schedule (per device, fwd+bwd) | '
        'ICI bytes/step | vs allgather |',
        '|---|---|---|---|',
    ]
    base = comm_model('allgather', n=n, b=b, h=h, t=t, d=d,
                      itemsize=itemsize)['total_bytes']
    for path in ('allgather', 'ring', 'ulysses'):
        m = comm_model(path, n=n, b=b, h=h, t=t, d=d, itemsize=itemsize)
        sched = '; '.join(f'{c}× {k} ({by / 2**20:.1f} MiB)'
                          for k, c, by in m['collectives'])
        lines.append(
            f"| {path} | {sched} | {m['total_bytes'] / 2**20:.0f} MiB "
            f"| {m['total_bytes'] / base:.2f}× |")
    gqa = comm_model('allgather', n=n, b=b, h=h, h_kv=max(1, h // 4),
                     t=t, d=d, itemsize=itemsize)['total_bytes']
    lines.append(
        f'| allgather + GQA (H_kv=H/4) | same schedule, H_kv heads | '
        f'{gqa / 2**20:.0f} MiB | {gqa / base:.2f}× |')
    return '\n'.join(lines)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument('--check', action='store_true',
                    help='compile on a virtual CPU mesh and reconcile '
                         'against the HLO collectives')
    ap.add_argument('--table', action='store_true',
                    help='emit the markdown table')
    ap.add_argument('-n', type=int, default=8)
    ap.add_argument('--heads', type=int, default=8)
    ap.add_argument('--seq-len', type=int, default=131072)
    ap.add_argument('--head-dim', type=int, default=96)
    args = ap.parse_args()
    if args.table:
        print(table_markdown(n=args.n, h=args.heads, t=args.seq_len,
                             d=args.head_dim))
    if args.check:
        import os
        import sys
        sys.path.insert(0, os.path.join(os.path.dirname(__file__), '..'))
        from distributed_dot_product_tpu._compat import ensure_cpu_devices
        ensure_cpu_devices(max(args.n, 8))
        ok = True
        for path, r in check_against_hlo(n=args.n).items():
            print(f"{path}: {'MATCH' if r['match'] else 'MISMATCH'} "
                  f"expected={r['expected']} got={r['got']}")
            ok = ok and r['match']
        raise SystemExit(0 if ok else 1)


if __name__ == '__main__':
    main()
