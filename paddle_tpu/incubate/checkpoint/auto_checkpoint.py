"""Auto checkpoint: train-loop-integrated save + crash recovery.

Reference analogue:
/root/reference/python/paddle/fluid/incubate/checkpoint/
auto_checkpoint.py:45 (AutoCheckpointChecker reads the EDL env,
TrainEpochRange:265 snapshots exe scope per epoch and `train_epoch_
range`:598 yields only the epochs not yet completed after a restart)
and checkpoint_saver.py (versioned save dirs, max_num_checkpoints).

TPU-native redesign: no ProgramDesc scope — the checkpoint is the
functional state (layer state_dict + optimizer state_dict + RNG seed)
written atomically with `framework.io.save`.  `train_epoch_range`
keeps the reference's contract: the SAME training script, run again
after a crash (e.g. restarted by `distributed.launch --elastic`),
skips the completed epochs and the model/optimizer resume from the
last snapshot — together they make a SIGKILLed job finish with the
same final state as an uninterrupted one.

Configuration is explicit (`configure(...)`) or by env like the
reference's PaddleCloud path: PADDLE_TPU_AUTO_CHECKPOINT_DIR enables
it, PADDLE_TPU_SAVE_CHECKPOINT_INTER (seconds) throttles saves.
Multi-host: only process 0 writes; every process reads the same dir
(shared filesystem, the reference's HDFS role).
"""
import os
import time
import warnings

from ...resilience import (
    install_shutdown, shutdown_requested, retry, PREEMPTED_EXIT_CODE,
    handler_installed, uninstall_shutdown)

__all__ = ['configure', 'train_epoch_range', 'train_step_range',
           'AutoCheckpointChecker']

_CKPT_NAME = 'acp_snapshot'

_state = {
    'dir': None,
    'model': None,
    'optimizer': None,
    'inter': None,
    'heartbeat': None,
    'last_save': 0.0,
    'graceful': True,
}


class AutoCheckpointChecker:
    """Env gate (reference auto_checkpoint.py:45): valid() iff an
    auto-checkpoint dir is configured explicitly or via env."""

    def __init__(self):
        self.env_dir = os.environ.get('PADDLE_TPU_AUTO_CHECKPOINT_DIR')
        self.save_checkpoint_inter = float(os.environ.get(
            'PADDLE_TPU_SAVE_CHECKPOINT_INTER', '0'))

    def valid(self):
        return (_state['dir'] or self.env_dir) is not None


def configure(checkpoint_dir=None, model=None, optimizer=None,
              save_checkpoint_inter=None, heartbeat_file=None,
              graceful_shutdown=True):
    """Register what a snapshot contains.  `model`/`optimizer` may be
    single objects or lists; both expose state_dict/set_state_dict.
    `heartbeat_file` is touched at every save so an elastic supervisor
    can detect a hung trainer.  With `graceful_shutdown` (default) a
    SIGTERM/SIGINT during a train range saves one final synchronous
    snapshot at the next step boundary and exits with
    resilience.PREEMPTED_EXIT_CODE — which distributed.elastic
    recognizes as a clean preemption (no restart budget consumed)."""
    _state['dir'] = checkpoint_dir
    _state['model'] = model
    _state['optimizer'] = optimizer
    _state['inter'] = save_checkpoint_inter
    _state['heartbeat'] = heartbeat_file
    _state['last_save'] = 0.0
    _state['graceful'] = graceful_shutdown


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _ckpt_path():
    d = _state['dir'] or os.environ.get(
        'PADDLE_TPU_AUTO_CHECKPOINT_DIR')
    return None if d is None else os.path.join(d, _CKPT_NAME)


def _save_snapshot(progress):
    """Atomic snapshot: write to a temp file in the same dir, fsync,
    rename — a crash mid-save leaves the previous snapshot intact
    (the reference's checkpoint_saver versioned-dir equivalent)."""
    path = _ckpt_path()
    if path is None:
        return
    _touch_heartbeat()   # EVERY host heartbeats, even non-writers —
    #                      each host's supervisor watches its own file
    import jax
    try:
        if jax.process_index() != 0:
            return
    except RuntimeError:
        pass
    import pickle
    import numpy as np

    def _host(o):
        """Recursively pull state to host numpy (device arrays and
        Tensor wrappers don't pickle portably)."""
        if isinstance(o, dict):
            return {k: _host(v) for k, v in o.items()}
        v = getattr(o, 'value', o)
        if isinstance(v, (int, float, str, bool, type(None))):
            return v
        return np.asarray(v)

    try:
        nprocs = jax.process_count()
    except RuntimeError:
        nprocs = 1
    payload = {
        'progress': progress,
        # snapshot state is host numpy — layout-free by construction —
        # but the WRITING topology is recorded so a restore onto a
        # different pool size is visible (elastic reshape), not silent
        'process_count': nprocs,
        'models': [_host(m.state_dict())
                   for m in _as_list(_state['model'])],
        'optimizers': [_host(o.state_dict())
                       for o in _as_list(_state['optimizer'])],
    }
    os.makedirs(os.path.dirname(path), exist_ok=True)
    from ...resilience import atomic_write
    retry(retries=2, backoff=0.05)(   # shared-fs writes flake; the
        lambda: atomic_write(         # tmp+replace makes retries safe
            path, lambda f: pickle.dump(payload, f), mode='wb',
            prefix='.acp_tmp'))()
    _state['last_save'] = time.time()


def _touch_heartbeat():
    """Mark this trainer live for the elastic supervisor.  The path
    comes from configure(heartbeat_file=...) or the
    PADDLE_TPU_HEARTBEAT_FILE env the launcher's --elastic mode
    exports to the worker."""
    hb = _state['heartbeat'] or os.environ.get(
        'PADDLE_TPU_HEARTBEAT_FILE')
    if hb:
        with open(hb, 'a'):
            os.utime(hb, None)


def _load_snapshot():
    path = _ckpt_path()
    if path is None or not os.path.exists(path):
        return None
    try:
        # AOT warm start: when tools/precompile.py left a sidecar
        # manifest next to the snapshot, pre-load the exported compile
        # artifacts so the restarted worker's first step deserializes
        # instead of re-paying trace+lower+compile
        from ...core import compile_cache
        compile_cache.warm_start(os.path.dirname(path),
                                 name='auto_checkpoint')
    except Exception:
        pass
    import pickle
    try:
        with open(path, 'rb') as f:
            payload = pickle.load(f)
    except (EOFError, pickle.UnpicklingError, OSError, ValueError) as e:
        # the write is atomic (tmp+replace), so a torn snapshot means
        # external damage; a restarted worker must start over, not
        # crash-loop on the same corrupt file
        warnings.warn(
            f'auto-checkpoint snapshot {path} is unreadable ({e}); '
            'starting from scratch', RuntimeWarning)
        return None
    saved_procs = payload.get('process_count')
    if saved_procs is not None:
        import jax
        try:
            nprocs = jax.process_count()
        except RuntimeError:
            nprocs = 1
        if nprocs != saved_procs:
            # elastic reshape: the snapshot is host numpy, so a
            # preempted pool resuming with fewer (or more) hosts
            # restores exactly — log it so the topology change is
            # auditable in the run report
            try:
                from ... import telemetry
                telemetry.event('reshape_restore',
                                saved_process_count=saved_procs,
                                process_count=nprocs, path=path)
            except Exception:
                pass
    for m, sd in zip(_as_list(_state['model']), payload['models']):
        m.set_state_dict(sd)
    for o, sd in zip(_as_list(_state['optimizer']),
                     payload['optimizers']):
        o.set_state_dict(sd)
    return payload['progress']


def _should_save():
    inter = _state['inter']
    if inter is None:
        inter = AutoCheckpointChecker().save_checkpoint_inter
    return (not inter) or (time.time() - _state['last_save'] >= inter)


def _range(kind, max_num):
    """Shared epoch/step generator: restore once, then yield only the
    remaining indices, snapshotting after each completed one.  Under
    graceful shutdown (configure default), a SIGTERM mid-range saves a
    final synchronous snapshot at the next boundary and exits
    PREEMPTED_EXIT_CODE — the elastic supervisor restarts without
    burning its failure budget and the resumed range loses zero
    completed work."""
    if not AutoCheckpointChecker().valid():
        # reference behaviour: without the env/config the range is a
        # plain range and nothing is saved
        yield from range(max_num)
        return
    # like Model.fit, the range only BORROWS the signal handlers: if
    # nothing else installed them, restore on exit so a later
    # Ctrl-C/SIGTERM behaves normally once the range is done
    owned = _state['graceful'] and not handler_installed()
    if _state['graceful']:
        install_shutdown()   # idempotent; no-op off the main thread
    try:
        progress = _load_snapshot()
        start = 0
        if progress is not None and progress.get('kind') == kind:
            start = int(progress.get('next', 0))
        for i in range(start, max_num):
            yield i
            if _state['graceful'] and shutdown_requested():
                # the completed index is durable BEFORE we bow out
                _save_snapshot({'kind': kind, 'next': i + 1})
                import signal
                import sys
                from ...resilience import (
                    preemption_signal, clear_shutdown)
                if preemption_signal() == signal.SIGINT:
                    # user interrupt, not fleet preemption: snapshot
                    # is saved, hand control back as Ctrl-C always has
                    clear_shutdown()
                    raise KeyboardInterrupt
                # preemption: durable flight-recorder dump next to the
                # snapshot so the restarted worker's post-mortem holds
                # the final grace-window timeline
                path = _ckpt_path()
                if path is not None:
                    from ... import telemetry
                    telemetry.dump_flight(os.path.join(
                        os.path.dirname(path),
                        f'flightrec-{kind}{i + 1}.json'))
                sys.exit(PREEMPTED_EXIT_CODE)
            if _should_save() or i == max_num - 1:
                _save_snapshot({'kind': kind, 'next': i + 1})
    finally:
        if owned:
            uninstall_shutdown()


def train_epoch_range(max_epoch_num, save_checkpoint_inter=None):
    """Reference auto_checkpoint.py:598: `for epoch in
    train_epoch_range(N):` — after a restart, completed epochs are
    skipped and model/optimizer state is restored."""
    if save_checkpoint_inter is not None:
        _state['inter'] = save_checkpoint_inter
    return _range('epoch', max_epoch_num)


def train_step_range(max_step_num, save_checkpoint_inter=None):
    """Step-granular variant (the TPU trainer's natural unit): same
    contract at per-step resolution, for jobs whose epochs are long
    enough that epoch snapshots lose too much work on a crash."""
    if save_checkpoint_inter is not None:
        _state['inter'] = save_checkpoint_inter
    return _range('step', max_step_num)
