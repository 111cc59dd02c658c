"""Device time by the program's own scopes and passes.

The program names its device work (``obs/spans.py``: ``DEVICE_SCOPES``,
opened with ``device_scope``; Pallas kernels carry the matching
``name=``). JAX writes the open scopes, with the transformations around
them (``jvp(…)``, ``transpose(jvp(…))``, ``checkpoint/
rematted_computation``), into every HLO instruction's
``metadata.op_name``, and the profiler's ``.xplane.pb`` carries each
compiled program whole: the ``/host:metadata`` plane holds one event
metadata a program whose ``Hlo Proto`` stat is the serialized
``HloProto``. jaxlib's ``ProfileData`` shows none of that, so this
module reads those few messages from the protobuf wire format itself
(``tensorflow`` has the generated classes, but importing it takes 9 s
and brings a second runtime into the process that holds the chip; the
tests check this reader against those classes where they import).

An instruction's ``op_name`` is, in this order: its own; for a fusion
(one event in the trace) the fused computation's root's, else that of
the named instruction nearest before the root (on the TPU the root is
often a nameless ``bitcast``); else, for what XLA put in itself (a
``copy`` of the layer scan's result, a ``get-tuple-element``), the
resolved ``op_name`` of what produced its first operand; else that of
the instruction that calls its computation (a nameless ``copy`` inside
the scan's ``while`` body belongs to the scan). So **a fusion counts
whole under one scope**, though XLA may have fused neighbours from two
scopes into it.

``scope_patterns.json`` turns an ``op_name`` into a class (ordered rows,
first match wins, innermost scopes first; what matches none is
``unattributed``) and a pass (``forward``, ``recompute``, ``backward``,
``optimizer``; else ``none``). The join to the trace is by instruction
name, on the rows ``trace.load_xplane`` made (self time, the busiest
device's plane): the same conventions as ``class_ms_per_step``, so the
classes partition ``model.xla_ms_per_step`` + the kernel metric.
"""

import functools
import os
import re

from benchmarks import loader, trace as tr

UNATTRIBUTED = 'unattributed'
NO_PASS = 'none'


def patterns():
    return loader.read_json(loader.HERE, 'scope_patterns.json')


# -- protobuf wire format ------------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7f) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def fields(buf):
    """``(field number, value)`` of one serialized message: an int for a
    varint, a memoryview for a length-delimited field; fixed-width
    fields are skipped (none of the messages read here keeps anything
    needed in one)."""
    buf = memoryview(buf)
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, value
        elif wire == 2:
            size, i = _varint(buf, i)
            yield number, buf[i:i + size]
            i += size
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
        else:
            raise ValueError(f'wire type {wire} at byte {i}')


def _text(view):
    return bytes(view).decode('utf-8', 'replace')


def _ints(value):
    """A repeated int64 field's value: packed, or one element."""
    if isinstance(value, int):
        return [value]
    out, i = [], 0
    while i < len(value):
        v, i = _varint(value, i)
        out.append(v)
    return out


# -- the trace file: the programs it carries ------------------------------

# Field numbers (tsl/profiler/protobuf/xplane.proto): XSpace.planes 1;
# XPlane.name 2, .event_metadata 4, .stat_metadata 5 (maps: key 1,
# value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, then one value field (.str_value 5,
# .bytes_value 6, an integer 3 or 4).
METADATA_PLANE = '/host:metadata'
HLO_STAT = 'Hlo Proto'


def planes(xspace):
    """``(plane name, {stat id: stat name}, [serialized XEventMetadata,
    ...])`` of each plane in the bytes of an ``.xplane.pb``; lines and
    events are skipped unread."""
    for number, plane in fields(xspace):
        if number != 1:
            continue
        name, stat_names, metas = '', {}, []
        for n, v in fields(plane):
            if n == 2:
                name = _text(v)
            elif n == 4:
                metas += [x for m, x in fields(v) if m == 2]
            elif n == 5:
                entry = dict(fields(v))
                stat_names[entry.get(1)] = next(
                    (_text(x) for m, x in fields(entry.get(2, b''))
                     if m == 2), '')
        yield name, stat_names, metas


def metadata_stats(meta, stat_names):
    """``(name, {stat name: value})`` of one XEventMetadata; a value is
    an int, or a memoryview for a string or bytes stat."""
    name, stats = '', {}
    for n, v in fields(meta):
        if n == 2:
            name = _text(v)
        elif n == 5:
            stat = dict(fields(v))
            key = stat_names.get(stat.pop(1, None), '')
            stats[key] = next(iter(stat.values()), None)
    return name, stats


def hlo_protos(xspace):
    """``[(program name, serialized HloProto), ...]`` from the bytes of
    an ``.xplane.pb``."""
    out = []
    for plane, stat_names, metas in planes(xspace):
        if plane != METADATA_PLANE:
            continue
        for meta in metas:
            program, stats = metadata_stats(meta, stat_names)
            if stats.get(HLO_STAT) is not None:
                out.append((program, stats[HLO_STAT]))
    return out


# -- one program: instruction -> op_name -----------------------------------

# Field numbers (xla/service/hlo.proto, xla/xla_data.proto):
# HloProto.hlo_module 1; HloModuleProto.computations 3,
# .entry_computation_id 6; HloComputationProto.name 1, .instructions 2,
# .id 5, .root_id 6; HloInstructionProto.name 1, .opcode 2, .metadata 7,
# .id 35, .operand_ids 36, .called_computation_ids 38;
# OpMetadata.op_name 2.

def computations(hlo_proto):
    """``({computation id: {'name', 'root_id', 'instructions': [{'name',
    'opcode', 'op_name', 'id', 'operands', 'calls'}, ...]}}, entry
    id)``."""
    module = next(v for n, v in fields(hlo_proto) if n == 1)
    comps, entry = {}, None
    for n, v in fields(module):
        if n == 6:
            entry = v
        if n != 3:
            continue
        comp = {'name': '', 'root_id': None, 'instructions': []}
        comp_id = None
        for cn, cv in fields(v):
            if cn == 1:
                comp['name'] = _text(cv)
            elif cn == 5:
                comp_id = cv
            elif cn == 6:
                comp['root_id'] = cv
            elif cn == 2:
                ins = {'name': '', 'opcode': '', 'op_name': '', 'id': None,
                       'operands': [], 'calls': []}
                for f, x in fields(cv):
                    if f == 1:
                        ins['name'] = _text(x)
                    elif f == 2:
                        ins['opcode'] = _text(x)
                    elif f == 35:
                        ins['id'] = x
                    elif f == 36:
                        ins['operands'] += _ints(x)
                    elif f == 38:
                        ins['calls'] += _ints(x)
                    elif f == 7:
                        ins['op_name'] = next(
                            (_text(y) for m, y in fields(x) if m == 2), '')
                comp['instructions'].append(ins)
        comps[comp_id] = comp
    return comps, entry


def _fused_op_name(comp):
    """The root's ``op_name``, else the nearest named instruction's
    before it (a computation lists operands before their users)."""
    named = [i for i in comp['instructions'] if i['op_name']]
    root = [i for i in named if i['id'] == comp['root_id']]
    return (root or named[-1:] or [{'op_name': ''}])[0]['op_name']


def op_names(hlo_proto):
    """``{instruction name: (op_name, how)}`` for every instruction that
    can be an event of its own (those of fused computations are not);
    ``how`` is ``own``, ``fused`` (from inside the fusion), ``operand``,
    ``caller`` or ``none``."""
    comps, entry = computations(hlo_proto)
    out, seen = {}, set()

    def walk(comp_id, inherited):
        if comp_id in seen:
            return
        seen.add(comp_id)
        produced = {}      # instruction id -> op_name it resolved to
        for ins in comps[comp_id]['instructions']:
            name, how = ins['op_name'], 'own'
            fusion = ins['opcode'] == 'fusion'
            if not name and fusion:
                name, how = _fused_op_name(comps[ins['calls'][0]]), 'fused'
            if not name and ins['operands']:
                name, how = produced.get(ins['operands'][0], ''), 'operand'
            if not name:
                name, how = inherited, 'caller' if inherited else 'none'
            produced[ins['id']] = name
            out[ins['name']] = (name, how)
            if not fusion:
                for called in ins['calls']:
                    walk(called, name)

    walk(entry, '')
    return out


# -- op_name -> (class, pass) -------------------------------------------------

def classify(op_name, pats):
    cls = next((c for c, rx in pats['classes'] if re.search(rx, op_name)),
               UNATTRIBUTED)
    pas = next((p for p, rx in pats['passes'] if re.search(rx, op_name)),
               NO_PASS)
    return cls, pas


@functools.lru_cache(maxsize=8)
def instruction_map(path):
    """``{instruction name: (class, pass, op_name, how)}`` over the
    programs of the trace at ``path``, parsed once a process. An
    instruction name that two programs give different classes or
    passes is ``unattributed``: the trace's events do not say which
    program they belong to."""
    pats = patterns()
    with open(path, 'rb') as f:
        data = f.read()
    merged = {}
    for _, proto in hlo_protos(data):
        for name, (op_name, how) in op_names(proto).items():
            row = (*classify(op_name, pats), op_name, how)
            if name in merged and merged[name][:2] != row[:2]:
                row = (UNATTRIBUTED, NO_PASS, '', 'ambiguous')
            merged[name] = row
    return merged


_NAME = re.compile(r'%([^\s=]+)')


def instruction_of(op):
    """``fusion.482`` from a trace row's ``%fusion.482 fusion``."""
    m = _NAME.search(op)
    return m.group(1) if m else op


def seconds_by_scope(ops, mapping, pats):
    """``{(class, pass): seconds of self time}`` over one device plane's
    rows. Collectives are left out, as ``model.xla_ms_per_step`` leaves
    them out: their time belongs to the communication metrics."""
    own_ns = {}
    for op, _, _, own in ops:
        own_ns[op] = own_ns.get(op, 0) + own
    out = {}
    for op, ns in own_ns.items():
        if tr.op_class(op, pats) == 'collective':
            continue
        key = mapping.get(instruction_of(op), (UNATTRIBUTED, NO_PASS))[:2]
        out[key] = out.get(key, 0.0) + ns / 1e9
    return out


def xplane_for(cell):
    """The traced run's file, found as ``run.py`` finds it; None unless
    there is exactly one."""
    from benchmarks import harness
    try:
        return harness.Tracer(os.path.join(
            loader.ROOT, '.bench_trace', cell.name)).xplane_path()
    except RuntimeError:
        return None


def run_seconds_by_scope(run):
    """``seconds_by_scope`` of a benchmark run's busiest device; None
    where there is nothing to read: no trace file, no program in it, or
    a program that opens no device scope at all (one from before the
    scopes: every metric would read 0 and ``unattributed`` the whole
    step, which says nothing of that program)."""
    path = xplane_for(run.cell)
    if path is None or not run.trace['devices']:
        return None
    mapping = instruction_map(path)
    if all(row[0] == UNATTRIBUTED for row in mapping.values()):
        return None
    ops = run.trace['devices'][tr.busiest(run.trace, run.patterns)]
    return seconds_by_scope(ops, mapping, run.patterns)
