"""What every driver shares: set-up phases on the host clock, the list of
numbers compared with their limits, the count of compilations inside the
window, and the profiler window with its host spans."""

import contextlib
import glob
import json
import os
import shutil
import time

import jax

PHASES = []          # [name, seconds, counted in setup_s, ended at]


def record_phase(name, seconds, counted=True):
    PHASES.append([name, seconds, counted, time.perf_counter()])
    print(json.dumps({'setup_part': name, 'seconds': round(seconds, 3),
                      'in_setup_s': counted}), flush=True)


@contextlib.contextmanager
def phase(name, counted=True):
    """Time one part of set-up and print it on a line of its own. The
    reference's time and the chip runtime's start (``counted=False``)
    are no part of ``setup_s``."""
    t0 = time.perf_counter()
    try:
        yield
    finally:
        record_phase(name, time.perf_counter() - t0, counted)


def uncounted_seconds(before):
    """Seconds of the parts that are no part of ``setup_s`` and ended
    before ``before`` (the decode cells' reference runs after the
    window, outside set-up already)."""
    return sum(dt for _, dt, counted, end in PHASES
               if not counted and end <= before)


class Compare:
    """Each number compared, beside its limit; ``correct`` is all of
    them. A limit of None is a reading with no limit set yet (the tools
    that read sound runs and controls use it) and fails no run."""

    def __init__(self):
        self.rows = []

    def add(self, name, value, limit, detail=None):
        value = float(value)
        ok = True if limit is None else bool(value <= limit)
        self.rows.append({'compared': name, 'value': value, 'limit': limit,
                          'ok': ok, 'detail': detail})
        print(json.dumps(self.rows[-1]), flush=True)

    @property
    def correct(self):
        return all(r['ok'] for r in self.rows)


class _Compiles:
    count = 0


@contextlib.contextmanager
def window_compiles():
    """Count backend compilations while the block runs."""
    from jax import monitoring
    box = _Compiles()

    def listen(event, duration, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            box.count += 1

    monitoring.register_event_duration_secs_listener(listen)
    try:
        yield box
    finally:
        monitoring.unregister_event_duration_listener(listen)


class Tracer:
    """The profiler around the traced window, writing to a fixed
    directory inside the checkout, with the benchmark's own host spans
    (``jax.profiler.TraceAnnotation``) on the same clock."""

    def __init__(self, directory):
        self.directory = directory
        self.on = False

    @contextlib.contextmanager
    def window(self, trace):
        if not trace:
            yield
            return
        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory)
        from distributed_dot_product_tpu import obs
        obs.enable(True)          # the program's spans join the trace
        jax.profiler.start_trace(self.directory)
        self.on = True
        try:
            yield
        finally:
            self.on = False
            jax.profiler.stop_trace()
            obs.enable(False)

    def span(self, name):
        if not self.on:
            return contextlib.nullcontext()
        return jax.profiler.TraceAnnotation(name)

    def xplane_path(self):
        found = glob.glob(os.path.join(self.directory, '**', '*.xplane.pb'),
                          recursive=True)
        if len(found) != 1:
            raise RuntimeError(f'expected one xplane file under '
                               f'{self.directory}, found {found}')
        return found[0]
