"""What every runner shares: finding a cell's files by the names in
BENCHMARK.json, the compile counter, the profiler window, the per-layer
readers, and the result line."""
import importlib
import json
import math
import os
import shutil
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(REPO, 'benchmark')
OUT_DIR = os.path.join(REPO, '.bench_out')


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, manifest=None):
    """Everything one cell needs, found by name: its entry, its
    configuration and traffic files, the end-to-end metrics it reports
    and the readers of its per-layer metrics."""
    manifest = manifest or load_json(os.path.join(REPO, 'BENCHMARK.json'))
    cells = {c['name']: c for c in manifest['workloads']}
    if workload not in cells:
        raise SystemExit(f'no workload {workload!r} in BENCHMARK.json '
                         f'(has {sorted(cells)})')
    cell = cells[workload]
    config_file = next(c['file'] for c in manifest['configs']
                       if c['name'] == cell['config'])

    def reported(metric):
        return workload in metric.get('workloads', [workload])

    # BENCHMARK.json declares a per-layer metric; its own file says
    # only which reader takes it, with which parameters
    layer = [{'name': m['name'], 'unit': m['unit'],
              **load_json(os.path.join(HERE, 'layer_metrics',
                                       m['name'] + '.json'))}
             for m in manifest['per_layer'] if reported(m)]
    return {
        'name': workload,
        'chips': int(cell['chips']),
        'config': load_json(os.path.join(REPO, config_file)),
        'traffic': load_json(os.path.join(
            HERE, 'traffic', cell['traffic'] + '.json')),
        'end_to_end': [m['name'] for m in manifest['end_to_end']
                       if reported(m)],
        'per_layer': layer,
    }


class CompileCounter:
    """jax's own compile events from here on: programs that reached
    the compiler (`built`: jax times the step whether the persistent
    cache served it or not), and that cache's hits and misses (the
    idea is chip_smoke.py's count_xla_cache_events)."""

    def __init__(self):
        from jax import monitoring
        self.built = self.hits = self.misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(
            self._on_duration)

    def _on_event(self, event, **_):
        if event == '/jax/compilation_cache/cache_hits':
            self.hits += 1
        elif event == '/jax/compilation_cache/cache_misses':
            self.misses += 1

    def _on_duration(self, event, _secs, **_kw):
        if event == '/jax/core/compile/backend_compile_duration':
            self.built += 1


def device_info():
    import jax
    devs = jax.devices()
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use')
             for d in devs]
    peaks = [p for p in peaks if p is not None]
    return {'platform': devs[0].platform, 'kind': devs[0].device_kind,
            'count': len(devs),
            'memory_peak_bytes': max(peaks) if peaks else None}


class TraceWindow:
    """One profiler session in the middle of the measured window, with
    the harness's 'bench.traced' span around exactly the traced work.
    `stall_s` is the time the profiler itself held the host."""

    def __init__(self, workload):
        self.dir = os.path.join(OUT_DIR, 'trace', workload)
        self.stall_s = 0.0
        self.open = False
        self.done = False
        self._span = None

    def start(self):
        import jax
        t0 = time.monotonic()
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        # the program names its own spans (PR 25), so jax's Python
        # tracer is off: with it a window of 4 s at 13 requests/s held
        # the engine 33 s and took minutes to read (PR 30)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        from benchmark.reduce_trace import TRACED_SPAN
        self._span = jax.profiler.TraceAnnotation(TRACED_SPAN)
        self._span.__enter__()
        self.open = True
        self.stall_s += time.monotonic() - t0

    def stop(self):
        import jax
        t0 = time.monotonic()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        self.open = False
        self.done = True
        self.stall_s += time.monotonic() - t0

    def load(self):
        """The reduced trace, or None where nothing was written."""
        from benchmark import reduce_trace
        path = reduce_trace.find_xplane(self.dir)
        if not self.done or path is None:
            return None
        return reduce_trace.Trace.from_file(path)


def read_layer_metrics(specs, ctx):
    """{name: {'value', 'unit'}} from each metric's own reader; a
    reader that finds nothing to read returns None and the metric is
    left out."""
    out = {}
    for spec in specs:
        reader = importlib.import_module(
            'benchmark.readers.' + spec['reader'])
        value = reader.read(spec.get('params', {}), ctx)
        if value is not None:
            out[spec['name']] = {'value': float(value),
                                 'unit': spec['unit']}
    return out


def result_line(cell, run, trace_on):
    """The one JSON object a run prints last.  `run` is the runner's
    dict: correct, attempted, failed, end_to_end {name: (value, unit)},
    counters, (traced) trace, and where the runner gives them
    `compared` and `device`."""
    import jax
    # a runner that runs a reference after its window hands over the
    # device as it read it before that
    device = run.get('device') or device_info()
    on_tpu = jax.default_backend() == 'tpu'
    line = {'correct': bool(run['correct']),
            'attempted': int(run['attempted']),
            'failed': int(run['failed'])}
    if not trace_on:
        line['metrics'] = {
            name: {'value': float(run['end_to_end'][name][0]),
                   'unit': run['end_to_end'][name][1]}
            for name in cell['end_to_end']}
        line['device'] = device
        return compared_last(line, run)
    from benchmark import reduce_trace
    trace = run.get('trace')
    summary = reduce_trace.summary(trace) if trace is not None else None
    ctx = {'chips': cell['chips'],
           'trace': trace if on_tpu else None,
           'trace_summary': summary if on_tpu else None,
           'counters': run['counters'], 'config': cell['config'],
           'traffic': cell['traffic'], 'device_kind': device['kind'],
           'on_tpu': on_tpu}
    line['metrics'] = read_layer_metrics(cell['per_layer'], ctx)
    if on_tpu and summary is not None:
        device['busy_s'] = summary['busy_s']
        device['window_s'] = summary['window_s']
    line['device'] = device
    if summary is not None:
        line['breakdown'] = summary['breakdown']
    return compared_last(line, run)


def compared_last(line, run):
    """The numbers `correct` was decided by, each beside its limit
    ({name: [number, limit]}), as the line's last key."""
    if run.get('compared'):
        line['compared'] = {
            name: [float(v), float(limit)]
            for name, (v, limit) in run['compared'].items()}
    return line


def percentile(values, q):
    """Nearest-rank percentile of a non-empty list."""
    vals = sorted(values)
    return vals[max(0, math.ceil(len(vals) * q) - 1)]
