"""Whole-loop compilation: K train steps fused into ONE XLA module.

The Julia full-compilation thesis (PAPERS.md, arxiv 1810.09868): on a
TPU the *program*, not the op or the step, is the compilation unit.
PRs 1-10 made the per-step module cheap to plan, cache and supervise,
but the epoch stayed a Python loop — per-step dispatch, callback
checks and telemetry ``observe()`` each ride a host round-trip, which
bounds step rate for exactly the small, high-QPS models (lenet,
widedeep-class) the north star cares about.

This module fuses K steps into one ``lax.scan``:

* the DataLoader's prefetched batches are STACKED with a leading K
  dim and the whole chunk is one dispatch;
* loss/metric scalars accumulate ON DEVICE inside the scan carry and
  come back as K-length stacked arrays, flushed once per chunk
  (``telemetry.StepAccumulator.observe_chunk`` expands them to
  per-step rows so run_report percentiles stay per-step);
* the NaN guard survives fusion: params ride the carry, the per-step
  finite mask rides the scan outputs, and :func:`cond_carry` keeps a
  non-finite step's update out of the carry with a ``lax.cond``
  rollback — ``nan_guard`` semantics are bit-identical to the
  unfused loop;
* the per-chunk step count comes back exact, so checkpoint and
  telemetry step ids never blur (preemption granularity becomes K
  steps — chunks end at the same boundaries checkpoints commit at);
* K composes with the PR-10 watchdog (:func:`clamp_chunk`: the chunk
  either fits inside the armed per-step budget or the budget is
  scaled to cover K steps) and with the PR-7 compile cache (callers
  fold K into the fingerprint so a fused module never collides with
  the per-step one).

``fused_steps`` is OFF by default everywhere; the
``PADDLE_TPU_FUSED_STEPS`` env var supplies a default K for runs that
cannot change code, and K=1 is bit-exact with today's per-step loop
(pinned by tests/test_fused_loop.py).
"""
import os
import queue
import threading
import time

__all__ = ['ENV_VAR', 'resolve_fused_steps', 'clamp_chunk',
           'cond_carry', 'stack_batches', 'chunk_sync',
           'fused_hapi_step', 'fused_trainer_step', 'fused_surrogate',
           'ChunkPrefetcher']

ENV_VAR = 'PADDLE_TPU_FUSED_STEPS'

_OFF = ('', '0', 'off', 'false', 'none', 'no')


def resolve_fused_steps(arg=None):
    """The chunk length a loop should fuse: an explicit ``fused_steps=``
    value wins (``False``/``0`` force off); ``None`` defers to the
    ``PADDLE_TPU_FUSED_STEPS`` env var — so any run can be fused
    without a code change.  Returns an int K >= 1, or 0 (off)."""
    if arg is None:
        arg = os.environ.get(ENV_VAR)
        if arg is None:
            return 0
    if arg is False:
        return 0
    if isinstance(arg, str):
        if arg.strip().lower() in _OFF:
            return 0
        arg = int(arg)
    k = int(arg)
    if k < 0:
        raise ValueError(f'fused_steps must be >= 0, got {k}')
    return k


def clamp_chunk(k, budget=None, est_step_s=None):
    """Adaptively clamp K against a watchdog step budget.

    The watchdog's contract is "one host-visible step completes within
    ``step_s``"; a fused chunk is one host-visible step that does K
    steps of work.  When a per-step wall estimate exists (the PR-6
    plan's ``est_us + compute_us``, or a measured step time), the
    chunk shrinks so K x estimate still fits inside the armed per-step
    deadline — detection latency for a hung chunk stays bounded by the
    budget the operator armed.  Without an estimate the caller instead
    scales the deadline to cover K steps (see
    ``ParallelTrainer.step_fused``).  Returns the (possibly smaller)
    chunk length, always >= 1."""
    k = max(1, int(k))
    if budget is None or not est_step_s or est_step_s <= 0:
        return k
    step_s = getattr(budget, 'step_s', None)
    if not step_s:
        return k
    return max(1, min(k, int(step_s // float(est_step_s))))


def cond_carry(ok, new_carry, old_carry):
    """In-loop rollback: select the new scan carry when the step was
    finite, else keep the old one — a ``lax.cond`` so a poisoned
    step's params/opt/buffers never enter the carry.  Both branches
    close over already-computed values, so under the scan this lowers
    to a select with no recompute; the semantics are the guarantee:
    ``nan_guard``'s skip contract survives fusion."""
    import jax
    return jax.lax.cond(ok, lambda: new_carry, lambda: old_carry)


def stack_batches(batches):
    """A list of K per-step batches (each a tuple/list of arrays) ->
    one tuple of arrays with a leading K dim, staged onto device.
    Host (numpy) fields stack on host and pay ONE device transfer per
    field; device fields stack ON DEVICE (no device->host readback —
    this is the hot staging path fusion exists to keep cheap)."""
    import numpy as np
    import jax.numpy as jnp
    if not batches:
        raise ValueError('stack_batches needs at least one batch')
    n_fields = len(batches[0])
    out = []
    for j in range(n_fields):
        col = [b[j] for b in batches]
        if all(isinstance(x, (np.ndarray, np.generic)) for x in col):
            out.append(jnp.asarray(np.stack(col)))
        else:
            out.append(jnp.stack([jnp.asarray(x) for x in col]))
    return tuple(out)


def chunk_sync(x):
    """THE one sanctioned host sync of a fused chunk: materialize the
    chunk's per-step finite mask (or any chunk-level device scalar)
    exactly once.  Runs inside an explicit transfer-guard allow block
    so the fused loops can be proven sync-free under
    ``transfer_guard_device_to_host('disallow')`` everywhere EXCEPT
    this call — and counted (``fused.chunk_syncs``) so the
    one-sync-per-chunk contract is testable, not aspirational."""
    import numpy as np
    import jax
    from .. import telemetry as _tel
    _tel.add('fused.chunk_syncs')
    with jax.transfer_guard_device_to_host('allow'):
        return np.asarray(x)


# -- fused step builders ------------------------------------------------------

def fused_hapi_step(step_fn, k):
    """Fuse hapi's per-step ``step_fn(params, buffers, opt_state,
    base_key, prev_step, lr, *arrays)`` into one K-step scan.

    The carry is (params, buffers, opt_state, step): the per-step
    dropout key (``fold_in(base_key, step)``) and the
    advance-on-finite step counter both live inside ``step_fn``, so
    the rng stream and the skip contract are bit-identical to K calls
    of the unfused module.  Outputs: final state + step, plus K-length
    stacked (losses, finite mask, metric stats) — the chunk's entire
    host-visible surface."""
    import jax

    def fused(params, buffers, opt_state, base_key, prev_step, lr,
              *stacked):
        def body(carry, xs):
            p, b, o, s = carry
            new_p, new_b, new_o, new_s, loss, ok, metrics = step_fn(
                p, b, o, base_key, s, lr, *xs)
            # step_fn already guards its own outputs (guard_update);
            # the cond re-states the rollback at the carry boundary so
            # a non-finite step can never advance the fused state
            new_carry = cond_carry(
                ok, (new_p, new_b, new_o, new_s), (p, b, o, s))
            return new_carry, (loss, ok, metrics)

        (p, b, o, s), (losses, oks, metrics) = jax.lax.scan(
            body, (params, buffers, opt_state, prev_step), stacked,
            length=k)
        return p, b, o, s, losses, oks, metrics

    return fused


def fused_trainer_step(step_fn, k, nan_guard=False):
    """Fuse ParallelTrainer's per-step ``step_fn(params, buffers,
    opt_state, step_no, key, *batch)`` into one K-step scan.

    Per-step PRNG keys arrive pre-split as a stacked (K, ...) array —
    the host draws them from the SAME ``rng_mod.next_key()`` stream
    the unfused loop consumes, so fused and unfused runs see identical
    dropout.  The optimizer step counter rides the carry and advances
    per finite step (Adam bias correction stays exact under skips)."""
    import jax

    def fused(params, buffers, opt_state, step_no0, keys, *stacked):
        def body(carry, xs):
            p, b, o, s = carry
            key, batch = xs[0], xs[1:]
            out = step_fn(p, b, o, s + 1, key, *batch)
            if nan_guard:
                new_p, new_b, new_o, loss, ok = out
                new_carry = cond_carry(
                    ok, (new_p, new_b, new_o, s + 1), (p, b, o, s))
                return new_carry, (loss, ok)
            new_p, new_b, new_o, loss = out
            return (new_p, new_b, new_o, s + 1), loss

        carry, ys = jax.lax.scan(
            body, (params, buffers, opt_state, step_no0),
            (keys,) + stacked, length=k)
        p, b, o, s = carry
        if nan_guard:
            losses, oks = ys
            return p, b, o, s, losses, oks
        return p, b, o, s, ys

    return fused


def fused_surrogate(step_fn, k):
    """Fuse an audit/AOT surrogate step (``analysis.targets.
    surrogate_step``: forward + loss + grad, no optimizer) into a
    K-step scan with on-device loss/grad accumulation — what
    ``tools/precompile.py --fused-steps`` lowers so a deploy's fused
    train module is warm before the first chunk runs."""
    import jax
    import jax.numpy as jnp

    def fused(params, buffers, key, *stacked):
        def body(carry, xs):
            g_acc, i = carry
            loss, grads = step_fn(params, buffers,
                                  jax.random.fold_in(key, i), *xs)
            g_acc = jax.tree_util.tree_map(jnp.add, g_acc, grads)
            return (g_acc, i + 1), loss

        zeros = jax.tree_util.tree_map(
            lambda v: jnp.zeros(v.shape, v.dtype), params)
        (grads, _), losses = jax.lax.scan(
            body, (zeros, jnp.zeros((), jnp.int32)), stacked, length=k)
        return losses, grads

    return fused


# -- chunk staging ------------------------------------------------------------

class ChunkPrefetcher:
    """Double-buffered device staging of K-batch chunks.

    Pulls K batches at a time from ``batch_iter``, runs ``stage_fn``
    (split + stack + device transfer) on a background thread so chunk
    N+1's host->device copy overlaps chunk N's execution, and yields
    ``(staged, n, wait_s)`` — ``wait_s`` is how long the consumer
    blocked on staging (the overlap gauge: ~0 when the double buffer
    hides the transfer).  A short tail (n < k) is yielded UNSTAGED as
    the raw batch list so the caller can run it through the per-step
    path instead of compiling a one-off K'-module.

    ``background=False`` (the num_workers=0 posture — there is no
    loader thread to overlap with) stages inline on the consumer
    thread; the iteration contract is identical.
    """

    def __init__(self, batch_iter, k, stage_fn, background=True,
                 depth=2):
        self.batch_iter = iter(batch_iter)
        self.k = max(1, int(k))
        self.stage_fn = stage_fn
        self.background = bool(background)
        self.depth = max(1, int(depth))
        self._q = None
        self._thread = None
        self._err = []
        self._closed = False

    def _pull_chunk(self):
        out = []
        for _ in range(self.k):
            try:
                out.append(next(self.batch_iter))
            except StopIteration:
                break
        return out

    def _stage(self, batches):
        if len(batches) == self.k:
            return (self.stage_fn(batches), self.k)
        return (batches, len(batches))       # unstaged tail

    def _put(self, item):
        while not self._closed:
            try:
                self._q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def _producer(self):
        try:
            while not self._closed:
                batches = self._pull_chunk()
                if not batches:
                    break
                if not self._put(self._stage(batches)):
                    return
        except BaseException as e:   # surface in the consumer
            self._err.append(e)
        finally:
            self._put(None)

    def __iter__(self):
        _perf = time.perf_counter
        if not self.background:
            while True:
                t0 = _perf()
                batches = self._pull_chunk()
                if not batches:
                    return
                staged, n = self._stage(batches)
                yield staged, n, _perf() - t0
            return
        self._q = queue.Queue(maxsize=self.depth)
        self._thread = threading.Thread(target=self._producer,
                                        daemon=True)
        self._thread.start()
        try:
            while True:
                t0 = _perf()
                item = self._q.get()
                wait_s = _perf() - t0
                if item is None:
                    if self._err:
                        raise self._err[0]
                    return
                staged, n = item
                yield staged, n, wait_s
        finally:
            # release a producer parked on a full queue so the daemon
            # thread exits with the epoch instead of leaking
            self._closed = True
            try:
                while True:
                    self._q.get_nowait()
            except queue.Empty:
                pass
            # bounded join: the producer's put-poll loop re-checks
            # _closed every 0.1s, so it exits within one poll tick —
            # the timeout only guards against a stage_fn hung on a
            # device transfer
            self._thread.join(timeout=2.0)
