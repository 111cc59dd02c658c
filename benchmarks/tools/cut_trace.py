"""Cut a profiler trace down to what ``benchmarks/tests/test_scopes.py``
keeps: the first ``--steps`` program executions of the first device
plane's operation line (each event's metadata shortened to
``%name = opcode()``, stats dropped) and the ``/host:metadata`` plane
with every ``Hlo Proto`` reduced to the fields ``benchmarks/scopes.py``
reads (names, opcodes, ``op_name``, ids, operands, called
computations). The result is still an ``.xplane.pb`` that
``jax.profiler.ProfileData`` and ``scopes.py`` both read.

This tool, and only this tool, needs ``tensorflow`` for the generated
protobuf classes.

    python benchmarks/tools/cut_trace.py in.xplane.pb out.xplane.pb --steps 2
"""

import argparse
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmarks import scopes, trace as tr  # noqa: E402


def reduced_hlo(hlo_pb2, data):
    full = hlo_pb2.HloProto.FromString(data)
    out = hlo_pb2.HloProto()
    out.hlo_module.name = full.hlo_module.name
    out.hlo_module.entry_computation_id = full.hlo_module.entry_computation_id
    for comp in full.hlo_module.computations:
        c = out.hlo_module.computations.add(
            name=comp.name, id=comp.id, root_id=comp.root_id)
        for ins in comp.instructions:
            i = c.instructions.add(name=ins.name, opcode=ins.opcode,
                                   id=ins.id)
            i.metadata.op_name = ins.metadata.op_name
            i.operand_ids.extend(ins.operand_ids)
            i.called_computation_ids.extend(ins.called_computation_ids)
    return out.SerializeToString()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('source')
    ap.add_argument('target')
    ap.add_argument('--steps', type=int, default=2)
    args = ap.parse_args()
    from tensorflow.compiler.xla.service import hlo_pb2
    from tensorflow.tsl.profiler.protobuf import xplane_pb2
    pats = tr.patterns()
    with open(args.source, 'rb') as f:
        full = xplane_pb2.XSpace.FromString(f.read())
    out = xplane_pb2.XSpace()
    device = next(p for p in full.planes
                  if re.search(pats['device_plane'], p.name))
    modules = next(line for line in device.lines
                   if line.name == 'XLA Modules')
    last = sorted(modules.events, key=lambda e: e.offset_ps)[args.steps - 1]
    end_ps = (modules.timestamp_ns * 1000 + last.offset_ps
              + last.duration_ps)
    plane = out.planes.add(id=device.id, name=device.name)
    for line in device.lines:
        if line.name != pats['op_line']:
            continue
        kept = plane.lines.add(id=line.id, name=line.name,
                               timestamp_ns=line.timestamp_ns)
        for e in line.events:
            if line.timestamp_ns * 1000 + e.offset_ps + e.duration_ps \
                    <= end_ps:
                kept.events.add(metadata_id=e.metadata_id,
                                offset_ps=e.offset_ps,
                                duration_ps=e.duration_ps)
                meta = device.event_metadata[e.metadata_id]
                name, _, opcode = tr.short_name(meta.name).partition(' ')
                plane.event_metadata[e.metadata_id].id = e.metadata_id
                plane.event_metadata[e.metadata_id].name = \
                    f'{name} = {opcode}()'
    for source in full.planes:
        if source.name != scopes.METADATA_PLANE:
            continue
        plane = out.planes.add(id=source.id, name=source.name)
        for key, stat in source.stat_metadata.items():
            plane.stat_metadata[key].CopyFrom(stat)
        for key, meta in source.event_metadata.items():
            kept = plane.event_metadata[key]
            kept.id, kept.name = meta.id, meta.name
            for stat in meta.stats:
                name = source.stat_metadata[stat.metadata_id].name
                if name == scopes.HLO_STAT:
                    kept.stats.add(
                        metadata_id=stat.metadata_id,
                        bytes_value=reduced_hlo(hlo_pb2, stat.bytes_value))
    with open(args.target, 'wb') as f:
        f.write(out.SerializeToString())
    print(f'{args.target}: {os.path.getsize(args.target)} bytes, '
          f'{args.steps} steps')


if __name__ == '__main__':
    main()
