"""A later PR adds a configuration, a mix, a driver kind, a reducer and a
per-layer metric as new files plus BENCHMARK.json entries, and edits no
file that is there."""

import json
import os

from benchmarks import loader


def write(path, text):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, 'w') as f:
        f.write(text)


def test_new_files_are_found_without_an_edit(tmp_path):
    root = str(tmp_path)
    bench = {
        'paths': ['benchmarks'],
        'configs': [{'name': 'newmodel', 'file':
                     'benchmarks/configs/newmodel.json'}],
        'workloads': [{'name': 'newmodel.echo', 'config': 'newmodel',
                       'traffic': 'echo-mix', 'chips': 1},
                      {'name': 'newmodel.other', 'config': 'newmodel',
                       'traffic': 'echo-mix', 'chips': 4}],
        'end_to_end': [{'name': 'setup_s', 'unit': 's'},
                       {'name': 'echo_per_s', 'unit': '1/s',
                        'workloads': ['newmodel.echo']}],
        'per_layer': [{'name': 'echo.count', 'unit': '1',
                       'workloads': ['newmodel.echo']},
                      {'name': 'other.count', 'unit': '1',
                       'workloads': ['newmodel.other']}],
    }
    write(f'{root}/BENCHMARK.json', json.dumps(bench))
    write(f'{root}/benchmarks/configs/newmodel.json',
          json.dumps({'reference': 'newref', 'width': 7}))
    write(f'{root}/benchmarks/traffic/echo-mix.json',
          json.dumps({'kind': 'echo', 'rate': 3}))
    write(f'{root}/benchmarks/limits/newmodel.echo.json',
          json.dumps({'limits': {'echo_gap': 0.5}}))
    write(f'{root}/benchmarks/drivers/echo.py', 'def run(cell):\n'
          '    return cell.traffic["rate"] * cell.config["width"]\n')
    write(f'{root}/benchmarks/reference/newref.py', 'NAME = "newref"\n')
    write(f'{root}/benchmarks/reducers/counter.py',
          'def read(run, metric):\n    return metric["scale"] * run\n')
    write(f'{root}/benchmarks/layer_metrics/echo.count.json',
          json.dumps({'reducer': 'counter', 'scale': 2, 'layer': 'echo'}))

    cell = loader.Cell('newmodel.echo', root=root)
    assert cell.kind == 'echo' and cell.chips == 1
    assert cell.limits == {'echo_gap': 0.5}
    assert cell.driver().run(cell) == 21
    assert cell.reference().NAME == 'newref'
    assert [m['name'] for m in cell.end_to_end()] == ['setup_s',
                                                      'echo_per_s']
    (metric,) = cell.per_layer()
    assert metric['name'] == 'echo.count' and metric['layer'] == 'echo'
    assert cell.reducer(metric['reducer']).read(5, metric) == 10


def test_every_cell_of_the_benchmark_loads():
    bench = loader.read_json(loader.ROOT, 'BENCHMARK.json')
    for w in bench['workloads']:
        cell = loader.Cell(w['name'])
        assert hasattr(cell.driver(), 'run')
        assert hasattr(cell.reference(), 'score_bias')
        assert cell.per_layer(), w['name']
        for m in cell.per_layer():
            assert hasattr(cell.reducer(m['reducer']), 'read')
        for key in cell.config['reduced']:
            assert key in cell.config, key
        assert cell.config['source'] == cell.config_entry['source']
        assert cell.config['reduced'] == cell.config_entry['reduced']


def test_unknown_device_kind_is_an_error():
    import pytest
    assert loader.peaks_for('TPU v5 lite')['flops_per_s'] == 197e12
    with pytest.raises(KeyError):
        loader.peaks_for('cpu')
