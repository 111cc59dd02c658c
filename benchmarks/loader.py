"""Find a cell's files by the names in BENCHMARK.json.

A later PR adds a configuration, a traffic mix, a driver kind, a reducer
or a per-layer metric as new files plus new BENCHMARK.json entries; no
file that is here needs an edit for it.
"""

import importlib.util
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def read_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def find(kind_dir, filename, root=ROOT):
    """``<root>/benchmarks/<kind_dir>/<filename>``, else the same under
    this checkout (a test root holds only what it adds)."""
    for base in (os.path.join(root, os.path.basename(HERE)), HERE):
        path = os.path.join(base, kind_dir, filename)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f'no {kind_dir}/{filename} under {root}')


def load_module(kind_dir, name, root=ROOT):
    """Import ``benchmarks/<kind_dir>/<name>.py`` by path."""
    path = find(kind_dir, f'{name}.py', root)
    spec = importlib.util.spec_from_file_location(
        f'benchmarks_{kind_dir}_{name.replace("-", "_").replace(".", "_")}',
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Cell:
    """One entry of ``workloads`` with its configuration, its traffic mix
    and the metrics it reports, all read from data files."""

    def __init__(self, workload, root=ROOT):
        self.root = root
        self.bench = read_json(root, 'BENCHMARK.json')
        cells = {w['name']: w for w in self.bench['workloads']}
        if workload not in cells:
            raise KeyError(f'unknown workload {workload!r}; BENCHMARK.json '
                           f'has {sorted(cells)}')
        self.entry = cells[workload]
        self.name = workload
        self.chips = int(self.entry['chips'])
        configs = {c['name']: c for c in self.bench['configs']}
        self.config_entry = configs[self.entry['config']]
        self.config = read_json(root, self.config_entry['file'])
        self.traffic = read_json(find(
            'traffic', f"{self.entry['traffic']}.json", root))
        self.kind = self.traffic['kind']
        # The limits of the numbers compared, with the readings they
        # were set from: one file a cell (PERF.md says how they are set).
        self.limits = read_json(find(
            'limits', f'{workload}.json', root))['limits']

    def _mine(self, metric):
        return self.name in metric.get('workloads', [self.name])

    def end_to_end(self):
        return [m for m in self.bench['end_to_end'] if self._mine(m)]

    def param_dtype(self):
        """The type the configuration states for its parameters."""
        import jax.numpy as jnp
        return jnp.dtype(self.config['precision']['params'])

    def reducer(self, name):
        return load_module('reducers', name, self.root)

    def per_layer(self):
        """The cell's per-layer metrics, each with the parameters its
        reader takes from ``layer_metrics/<name>.json``."""
        return [{**read_json(find('layer_metrics', f"{m['name']}.json",
                                  self.root)), **m}
                for m in self.bench['per_layer'] if self._mine(m)]

    def driver(self):
        return load_module('drivers', self.kind, self.root)

    def reference(self):
        return load_module('reference', self.config['reference'],
                           self.root)


def peaks_for(device_kind):
    table = read_json(HERE, 'peaks.json')['by_device_kind']
    if device_kind not in table:
        raise KeyError(f'no peaks for device kind {device_kind!r} in '
                       f'benchmarks/peaks.json: add a row with its source')
    return table[device_kind]
