"""Training-robustness utilities (SURVEY.md §2 item 39; reference:
fleet launch_utils watchdogs + debug tooling).

- NaN/Inf detection: `debug_nans` (XLA-level trap) and `check_numerics`
  (explicit guard for compiled steps).
- Watchdog: wall-clock heartbeat monitor for hung steps (a stuck ICI
  collective or input pipeline shows up as a missed heartbeat).
- try_load_latest / save_step: step-level checkpoint/resume helpers used
  with paddle_tpu.save/load for elastic restarts.
"""
import os
import threading
import time
import warnings

import numpy as np
import jax
import jax.numpy as jnp

__all__ = ['debug_nans', 'check_numerics', 'Watchdog', 'save_step',
           'try_load_latest']


def debug_nans(enable=True):
    """XLA-level NaN trap: any op producing NaN raises immediately
    (reference analogue: FLAGS_check_nan_inf)."""
    jax.config.update('jax_debug_nans', bool(enable))


def check_numerics(tree, name='tensors'):
    """Host-side finite check over a pytree of arrays; raises
    FloatingPointError naming the first offending leaf."""
    from ..core.tensor import Tensor
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(
        jax.tree_util.tree_map(
            lambda v: v.value if isinstance(v, Tensor) else v, tree))[0]
    for path, leaf in leaves_with_paths:
        arr = np.asarray(leaf)
        if arr.dtype.kind in 'fc' and not np.isfinite(arr).all():
            where = '/'.join(str(getattr(k, 'key', getattr(k, 'idx', k)))
                             for k in path)
            raise FloatingPointError(
                f'non-finite values in {name}[{where}]')
    return True


class Watchdog:
    """Fires `on_stall` if `beat()` is not called within `timeout_s`.

    Use around training loops: a hung collective, a hung input
    pipeline or a dead worker surfaces as a stall instead of silence.
    """

    def __init__(self, timeout_s=300.0, on_stall=None, name='train'):
        self.timeout_s = timeout_s
        self.on_stall = on_stall
        self.name = name
        self._last = time.monotonic()
        self._stop = threading.Event()
        self._thread = None
        self.stalled = False

    def _run(self):
        while not self._stop.wait(min(self.timeout_s / 4, 10.0)):
            if time.monotonic() - self._last > self.timeout_s:
                self.stalled = True
                msg = (f'watchdog[{self.name}]: no heartbeat for '
                       f'{self.timeout_s:.0f}s')
                if self.on_stall is not None:
                    self.on_stall(msg)
                else:
                    warnings.warn(msg)
                self._last = time.monotonic()  # don't spam

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()
        return self

    def beat(self):
        self._last = time.monotonic()
        self.stalled = False

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=1.0)

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()


def save_step(state_dict, directory, step, keep=3, prefix='ckpt'):
    """Write `<dir>/<prefix>_<step>.pdparams` and prune old ones."""
    from ..framework.io import save
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f'{prefix}_{step}.pdparams')
    save(state_dict, path)
    # prune (ignore non-numeric suffixes: foreign files in the dir)
    ckpts = sorted(
        (f for f in os.listdir(directory)
         if f.startswith(prefix + '_') and f.endswith('.pdparams')
         and f[len(prefix) + 1:-len('.pdparams')].isdigit()),
        key=lambda f: int(f[len(prefix) + 1:-len('.pdparams')]))
    for old in ckpts[:-keep]:
        try:
            os.remove(os.path.join(directory, old))
        except OSError:
            pass
    return path


def try_load_latest(directory, prefix='ckpt'):
    """Return (state_dict, step) for the newest checkpoint, or
    (None, -1) when none exists — elastic-restart entry point."""
    from ..framework.io import load
    if not os.path.isdir(directory):
        return None, -1
    ckpts = sorted(
        (f for f in os.listdir(directory)
         if f.startswith(prefix + '_') and f.endswith('.pdparams')
         and f[len(prefix) + 1:-len('.pdparams')].isdigit()),
        key=lambda f: int(f[len(prefix) + 1:-len('.pdparams')]))
    if not ckpts:
        return None, -1
    newest = ckpts[-1]
    step = int(newest[len(prefix) + 1:-len('.pdparams')])
    return load(os.path.join(directory, newest)), step


# -- reference paddle.utils surface ------------------------------------------

def deprecated(update_to='', since='', reason='', level=0):
    """Decorator marking an API deprecated (reference
    utils/deprecated.py): appends a note to the docstring and warns on
    call.  Levels match the reference: 0/1 warn, 2 raises."""
    import functools
    import warnings

    def wrap(fn):
        msg = f'API "{fn.__module__}.{fn.__name__}" is deprecated'
        if since:
            msg += f' since {since}'
        if update_to:
            msg += f', use "{update_to}" instead'
        if reason:
            msg += f'; reason: {reason}'
        fn.__doc__ = (fn.__doc__ or '') + f'\n\n    .. warning:: {msg}\n'

        @functools.wraps(fn)
        def inner(*args, **kwargs):
            if level >= 2:
                raise RuntimeError(msg)
            warnings.warn(msg, DeprecationWarning, stacklevel=2)
            return fn(*args, **kwargs)
        return inner
    return wrap


def run_check():
    """Installation self-check (reference utils/install_check.py):
    run a tiny compiled train step on the default device and report."""
    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    x = jnp.ones((4, 8))
    w = jnp.ones((8, 2)) * 0.1

    @jax.jit
    def step(w):
        loss = ((x @ w) ** 2).mean()
        return loss, jax.grad(lambda w: ((x @ w) ** 2).mean())(w)

    loss, g = step(w)
    jax.block_until_ready(g)
    assert bool(jnp.isfinite(loss)), 'non-finite loss in run_check'
    print(f'paddle_tpu is installed successfully! '
          f'(compiled a train step on {dev.platform}:{dev.id})')


def require_version(min_version, max_version=None):
    """Raise unless min_version <= __version__ (<= max_version)
    (reference utils/__init__.py::require_version)."""
    from .. import __version__

    def key(v):
        return [int(p) for p in str(v).replace('-', '.').split('.')
                if p.isdigit()]
    cur = key(__version__)
    if key(min_version) > cur:
        raise Exception(
            f'paddle_tpu>={min_version} required, found {__version__}')
    if max_version is not None and key(max_version) < cur:
        raise Exception(
            f'paddle_tpu<={max_version} required, found {__version__}')


def try_import(module_name, err_msg=None):
    """Import a soft dependency with an actionable error (reference
    utils/lazy_import.py::try_import)."""
    import importlib
    try:
        return importlib.import_module(module_name)
    except ImportError as e:
        raise ImportError(
            err_msg or f"Failed to import '{module_name}'; this "
            f"environment is zero-egress, so only baked-in packages "
            f"are importable") from e


from . import unique_name  # noqa: E402,F401
from . import download  # noqa: E402,F401
from . import cpp_extension  # noqa: E402,F401
from . import profiler  # noqa: E402,F401
from ..dataset import image as image_util  # noqa: E402,F401
from ..profiler import Profiler  # noqa: E402,F401

__all__ += ['deprecated', 'run_check', 'require_version', 'try_import',
            'unique_name', 'download', 'cpp_extension', 'profiler',
            'image_util', 'Profiler']
