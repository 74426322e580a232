"""High-level Model API: fit / evaluate / predict / save / load.

Reference analogue: python/paddle/hapi/model.py (class Model).  The
reference dispatches per-batch through the dygraph tracer or a static
Program; here `fit` compiles ONE jitted train step — forward + loss +
grad + optimizer update + metric pre-compute — into a single XLA module
with donated params/opt-state (in-place HBM update), and the epoch loop
stays host-side.  That is the whole TPU story: the MXU sees one fused
program per step, the host only feeds batches.
"""
import os
import signal as _signal
import warnings

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..jit import functional_call
from ..io import DataLoader, Dataset
from ..framework.io import save as _save, load as _load
from ..metric import Metric
from ..resilience import (
    finite_step as _finite_step, guard_update as _guard_update,
    install_shutdown as _install_shutdown,
    shutdown_requested as _shutdown_requested)
from .callbacks import config_callbacks

__all__ = ['Model']


def _to_jnp(x):
    if isinstance(x, Tensor):
        return x.value
    return jnp.asarray(x)


def _as_list(x):
    if x is None:
        return []
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _outs_list(outs):
    """functional_call returns the layer's output pytree verbatim — a bare
    array for single-output layers; normalize to a list."""
    return list(outs) if isinstance(outs, (list, tuple)) else [outs]


class Model:
    """Wraps a Layer with train/eval/predict loops over compiled steps.

    Args:
        network: paddle_tpu.nn.Layer with forward(*inputs).
        inputs/labels: optional InputSpec lists (count determines the
            input/label split of each batch; default 1 label).
    """

    def __init__(self, network, inputs=None, labels=None):
        self.network = network
        self._inputs = _as_list(inputs)
        self._labels = _as_list(labels)
        self._optimizer = None
        self._loss = None
        self._metrics = []
        self._lint = None
        self.stop_training = False
        # True (default): train_batch materializes the per-step
        # finiteness flag so skipped steps feed no metrics and
        # NanGuard sees a Python bool — one host sync per step, the
        # price of the exact skip contract.  NanGuard(enable=False)
        # flips this off for the sync-free fast path: the loss / ok
        # stay device arrays, the step counter advances on device, and
        # skipped steps contribute zeroed (masked) metric stats.
        self._check_finite_steps = True
        # compiled-step caches, keyed by (shapes, dtypes, lr-if-constant)
        self._train_step_cache = {}
        self._train_chunk_cache = {}    # fused K-step modules
        self._eval_step_cache = {}
        self._pred_step_cache = {}
        # functional state lives here between steps (device pytrees)
        self._fstate = None
        # divergence sentinel plumbing: last-known-good snapshot for
        # rollback + the per-step finiteness flag NanGuard reads
        self._good_state = None
        self._last_step_ok = True

    # -- preparation ---------------------------------------------------------
    def prepare(self, optimizer=None, loss=None, metrics=None,
                amp_configs=None, lint=None):
        self._optimizer = optimizer
        self._loss = loss
        self._metrics = _as_list(metrics)
        for m in self._metrics:
            assert isinstance(m, Metric), \
                'metrics must be paddle_tpu.metric.Metric instances'
        self._amp = amp_configs or {}
        # lint: run the paddle_tpu.analysis TPU lint over each newly
        # compiled train step (jaxpr rules incl. donation audit) and
        # over the network's forward source — None/False off,
        # 'warn'/True warns, 'error' raises on high severity
        self._lint = lint
        # a new optimizer/loss invalidates compiled steps (their traces
        # closed over the old ones) and the functional state
        self._train_step_cache.clear()
        self._train_chunk_cache.clear()
        self._eval_step_cache.clear()
        self._pred_step_cache.clear()
        self._invalidate()
        return self

    def parameters(self, *args, **kwargs):
        return self.network.parameters()

    # -- functional state sync -----------------------------------------------
    def _get_fstate(self):
        if self._fstate is None:
            params, buffers = self.network.functional_state()
            # copy: the compiled step donates its inputs, and these arrays
            # are aliased by the live eager Parameters
            params = jax.tree_util.tree_map(
                lambda v: jnp.array(v, copy=True), params)
            buffers = jax.tree_util.tree_map(
                lambda v: jnp.array(v, copy=True), buffers)
            if self._optimizer is not None:
                # resume from eager accumulators (set by load()) when present
                live = dict(self.network.named_parameters())
                acc = self._optimizer._accumulators
                opt_state = {
                    n: jax.tree_util.tree_map(
                        lambda v: jnp.array(v, copy=True), acc[id(p)])
                    if id(p) in acc
                    else self._optimizer._create_state(p.value)
                    for n, p in live.items()}
                step = self._optimizer._global_step
            else:
                opt_state, step = {}, 0
            self._fstate = {'params': params, 'buffers': buffers,
                            'opt': opt_state, 'step': step}
        return self._fstate

    def _sync_back(self):
        """Write device pytrees back into the eager Layer tree and the
        optimizer's accumulators (so state_dict/save see trained state).
        Copies: the next compiled step donates the fstate arrays."""
        if self._fstate is None:
            return
        cp = lambda v: jnp.array(v, copy=True)  # noqa: E731
        self.network.load_functional_state(
            jax.tree_util.tree_map(cp, self._fstate['params']),
            jax.tree_util.tree_map(cp, self._fstate['buffers']))
        if self._optimizer is not None:
            live = dict(self.network.named_parameters())
            for n, st in self._fstate['opt'].items():
                if n in live:
                    self._optimizer._accumulators[id(live[n])] = \
                        jax.tree_util.tree_map(cp, st)
            # the sync-free step path advances the counter on device;
            # materialize it here (an epoch/save boundary) so
            # state_dict round-trips a plain int
            self._optimizer._global_step = int(
                np.asarray(self._fstate['step']))

    def _invalidate(self):
        """Eager params changed (load/user edit): drop functional state."""
        self._fstate = None

    # -- divergence rollback (resilience.NanSentinel policy) -----------------
    def _copy_tree(self, t):
        return jax.tree_util.tree_map(
            lambda v: jnp.array(v, copy=True) if hasattr(v, 'dtype')
            else v, t)

    def _capture_good_state(self):
        """Snapshot the functional state as the rollback target.
        Copies are mandatory: the compiled step donates the live
        fstate arrays, so an aliased snapshot would be deleted out
        from under us by the very next step."""
        st = self._get_fstate()
        self._good_state = {'params': self._copy_tree(st['params']),
                            'buffers': self._copy_tree(st['buffers']),
                            'opt': self._copy_tree(st['opt']),
                            'step': st['step']}

    def _rollback_to_good_state(self):
        """Restore the last captured snapshot (NanGuard calls this
        after K consecutive non-finite steps).  -> True if a snapshot
        existed.  The snapshot itself is re-copied so repeated
        rollbacks keep working."""
        if self._good_state is None:
            return False
        g = self._good_state
        self._fstate = {'params': self._copy_tree(g['params']),
                        'buffers': self._copy_tree(g['buffers']),
                        'opt': self._copy_tree(g['opt']),
                        'step': g['step']}
        if self._optimizer is not None:
            self._optimizer._global_step = g['step']
        return True

    # -- compiled steps ------------------------------------------------------
    def _loss_value(self, outs, labels):
        outs_t = [Tensor._from_value(o) for o in outs]
        labels_t = [Tensor._from_value(l) for l in labels]
        if self._loss is None:
            lv = outs[0]
        else:
            lv = self._loss(*(outs_t + labels_t))
            lv = lv.value if isinstance(lv, Tensor) else jnp.asarray(lv)
        return jnp.mean(lv)

    def _metric_computes(self, outs, labels):
        res = []
        for m in self._metrics:
            if labels:
                r = m.compute(outs[0], labels[0])
            else:
                r = m.compute(outs[0])
            res.append(r.value if isinstance(r, Tensor) else r)
        return res

    def _batch_key(self, arrays, extra=()):
        sig = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
        return sig + tuple(extra)

    def _build_train_step(self, n_in):
        """The raw (unjitted) step — also what prepare(lint=...)
        audits, so the linter sees exactly what XLA compiles."""
        network, opt = self.network, self._optimizer

        def step_fn(params, buffers, opt_state, base_key, prev_step, lr,
                    *arrays):
            inputs, labels = arrays[:n_in], arrays[n_in:]
            # the per-step dropout key (fold of paddle.seed with the
            # step counter) and the counter increment both live INSIDE
            # the module: the sync-free path then issues zero per-step
            # host-side dispatches beyond this one call
            step = prev_step + 1
            key = jax.random.fold_in(base_key, prev_step)

            def loss_fn(p):
                outs, new_buf = functional_call(
                    network, p, buffers, inputs, key=key, training=True)
                outs = _outs_list(outs)
                return self._loss_value(outs, labels), (outs, new_buf)

            (loss, (outs, new_buf)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            # divergence sentinel, device side: a non-finite
            # loss/grad step keeps the OLD params/opt/buffers — the
            # update is skipped inside the same XLA module, composing
            # with the amp GradScaler's found_inf skip on the eager
            # path.  Host-side policy (strike counting, rollback)
            # lives in callbacks.NanGuard.
            ok = _finite_step(loss, grads)
            # lr is a traced arg: scheduler steps / set_lr reach the
            # compiled module without retracing
            new_params, new_opt = opt.apply_gradients(
                params, grads, opt_state, step, lr=lr)
            new_params = _guard_update(ok, new_params, params)
            new_opt = _guard_update(ok, new_opt, opt_state)
            new_buf = _guard_update(ok, new_buf, buffers)
            # metric stats are masked ON DEVICE for skipped steps so
            # the sync-free path can feed them without reading `ok`
            # back (neutral adds for count-style metrics)
            metrics = [jax.tree_util.tree_map(
                lambda v: jnp.where(ok, v, jnp.zeros_like(v)), r)
                for r in self._metric_computes(outs, labels)]
            new_step = prev_step + ok.astype(jnp.int32)
            return (new_params, new_buf, new_opt, new_step, loss, ok,
                    metrics)

        return step_fn

    def _make_train_step(self, n_in):
        return jax.jit(self._build_train_step(n_in),
                       donate_argnums=(0, 1, 2))

    def _make_eval_step(self, n_in):
        network = self.network

        def step_fn(params, buffers, key, *arrays):
            inputs, labels = arrays[:n_in], arrays[n_in:]
            outs, _ = functional_call(network, params, buffers, inputs,
                                      key=key, training=False)
            outs = _outs_list(outs)
            loss = self._loss_value(outs, labels) \
                if self._loss is not None else jnp.zeros(())
            metrics = self._metric_computes(outs, labels)
            return outs, loss, metrics

        return jax.jit(step_fn)

    def _make_pred_step(self, n_in):
        network = self.network

        def step_fn(params, buffers, key, *arrays):
            outs, _ = functional_call(network, params, buffers,
                                      arrays[:n_in], key=key,
                                      training=False)
            return _outs_list(outs)

        return jax.jit(step_fn)

    def _split_arity(self, n_fields):
        """How many leading fields of an n_fields batch feed forward
        (the rest are labels) — shape logic only, no conversion."""
        n_lab = len(self._labels) if self._labels else \
            (1 if self._loss is not None else 0)
        n_lab = min(n_lab, max(0, n_fields - 1))
        return n_fields - n_lab

    def _split_batch(self, batch):
        batch = [_to_jnp(b) for b in _as_list(batch)]
        return batch, self._split_arity(len(batch))

    # -- public batch APIs ---------------------------------------------------
    def train_batch(self, inputs, labels=None):
        """One compiled optimizer step; returns (loss, metric_results).

        The loss comes back as a DEVICE scalar (host-sync lint: the
        old ``float(loss)`` here stalled the XLA queue every step —
        see PERF.md).  ``float(loss)`` still works for callers that
        want a number; the fit loop materializes only when a logger
        actually prints."""
        assert self._optimizer is not None and self._loss is not None, \
            'call prepare(optimizer, loss) before train_batch'
        batch = _as_list(inputs) + _as_list(labels)
        arrays, n_in = self._split_batch(batch)
        st = self._get_fstate()
        key = self._batch_key(arrays, ('train', n_in))
        first_call = key not in self._train_step_cache
        if first_call:
            if self._lint:
                self._lint_train_step(n_in, st, arrays)
            jitted = self._make_train_step(n_in)
            from ..core import compile_cache as _cc
            if _cc.enabled():
                # persistent executable cache (core.compile_cache): a
                # restarted process deserializes the exported step
                # instead of re-tracing; cold path unchanged (donating
                # jit) and additionally exported for the next process
                example = (st['params'], st['buffers'], st['opt'],
                           jax.random.PRNGKey(0),
                           jnp.zeros((), jnp.int32),
                           jnp.zeros((), jnp.float32), *arrays)
                fp = _cc.jaxpr_fingerprint(
                    'hapi-train', self._build_train_step(n_in), example,
                    extra=('donate', (0, 1, 2)))
                jitted = _cc.through_cache(jitted, example, fp=fp,
                                           name='Model.train_batch')
            # memory observatory, armed-only (one extra lower+compile
            # per variant): XLA memory_analysis vs liveness prediction
            from ..telemetry import memory as _mem
            _mem.ensure_sampler()
            if _mem.armed():
                _mem.maybe_note_compiled(
                    'Model.train_batch', jitted,
                    (st['params'], st['buffers'], st['opt'],
                     jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
                     jnp.zeros((), jnp.float32), *arrays),
                    source='hapi')
            self._train_step_cache[key] = jitted
            from ..analysis import note_retrace
            note_retrace('Model.train_batch',
                         len(self._train_step_cache), instance=self)
        fn = self._train_step_cache[key]
        # base dropout key derived from the user's paddle.seed (the
        # engine's core.rng) — the per-step fold with the counter
        # happens inside the compiled module; cache the PRNGKey until
        # the user reseeds
        from ..core import rng as rng_mod
        seed = rng_mod.get_seed()
        if getattr(self, '_base_key_seed', None) != seed:
            self._base_key = jax.random.PRNGKey(seed)
            self._base_key_seed = seed
        # optimizer rules take t starting at 1 (Adam bias correction —
        # step_fn derives t = prev_step + 1 on device)
        if first_call:
            import time as _time
            _ct0 = _time.perf_counter()
        new_params, new_buf, new_opt, new_step, loss, ok, mres = fn(
            st['params'], st['buffers'], st['opt'], self._base_key,
            jnp.asarray(st['step'], jnp.int32),
            jnp.asarray(self._optimizer.get_lr(), jnp.float32), *arrays)
        if first_call:
            # the first call of a new cache entry traces + XLA-compiles
            # synchronously before dispatching, so this delta IS the
            # compile cost (execution itself stays async)
            from .. import telemetry
            _dt = _time.perf_counter() - _ct0
            telemetry.event('compile', name='Model.train_batch',
                            dur_s=round(_dt, 6),
                            variants=len(self._train_step_cache))
            telemetry.add('compile.count')
            telemetry.add('compile.total_s', _dt)
        # donation invalidated the inputs — always adopt the returned
        # arrays (they hold the OLD values when the step was skipped)
        if self._check_finite_steps:
            # exact-skip contract: materialize ok (one host sync) so a
            # skipped step feeds no metrics and no optimizer tick
            ok = bool(ok)
            self._last_step_ok = ok
            st.update(params=new_params, buffers=new_buf, opt=new_opt,
                      step=st['step'] + (1 if ok else 0))
            self._optimizer._global_step = st['step']
            if not ok:
                # policy (strikes/rollback) is NanGuard's
                return loss, []
        else:
            # sync-free path: nothing here reads a device value — the
            # host runs ahead and keeps the XLA queue full.  `ok`
            # stays a device bool (NanGuard, if someone re-enables it,
            # pays the sync), the step counter advanced on device, and
            # mres was already masked to zero inside the module
            self._last_step_ok = ok
            st.update(params=new_params, buffers=new_buf, opt=new_opt,
                      step=new_step)
            self._optimizer._global_step = st['step']
        metric_logs = [m.update(r) if not isinstance(r, (tuple, list))
                       else m.update(*r)
                       for m, r in zip(self._metrics, mres)]
        return loss, metric_logs

    # -- fused K-step chunks (core.scan_loop) --------------------------------
    def train_chunk(self, stacked, n_in=None, k=None):
        """K compiled optimizer steps in ONE dispatch (whole-loop
        compilation, core.scan_loop): `stacked` is the chunk's batch —
        each array carries a leading K dim — and the call returns
        ``(losses, oks)`` as K-length DEVICE arrays.  The rng stream,
        skip contract and update math are bit-exact with K calls of
        :meth:`train_batch` (pinned by tests/test_fused_loop.py);
        what changes is cadence: ONE host round-trip per chunk, and
        under the default exact-skip posture ONE host sync per chunk
        (the finite-mask readback, ``scan_loop.chunk_sync``)."""
        assert self._optimizer is not None and self._loss is not None, \
            'call prepare(optimizer, loss) before train_chunk'
        import time as _time
        from ..core import scan_loop as _scan
        stacked = tuple(_to_jnp(v) for v in stacked)
        k = int(k if k is not None else stacked[0].shape[0])
        if n_in is None:
            _, n_in = self._split_batch([v[0] for v in stacked])
        st = self._get_fstate()
        key = self._batch_key(stacked, ('train-fused', n_in, k))
        first_call = key not in self._train_chunk_cache
        if first_call:
            if self._lint:
                self._lint_train_step(
                    n_in, st, [v[0] for v in stacked], fused=k)
            fused_fn = _scan.fused_hapi_step(
                self._build_train_step(n_in), k)
            jitted = jax.jit(fused_fn, donate_argnums=(0, 1, 2))
            from ..core import compile_cache as _cc
            if _cc.enabled():
                # the fused module rides the same persistent cache as
                # the per-step one; K folds into the fingerprint so
                # the two can never collide
                example = (st['params'], st['buffers'], st['opt'],
                           jax.random.PRNGKey(0),
                           jnp.zeros((), jnp.int32),
                           jnp.zeros((), jnp.float32), *stacked)
                fp = _cc.jaxpr_fingerprint(
                    'hapi-train-fused', fused_fn, example,
                    extra=('donate', (0, 1, 2), 'fused', k))
                jitted = _cc.through_cache(jitted, example, fp=fp,
                                           name='Model.train_chunk')
            from ..telemetry import memory as _mem
            if _mem.armed():
                _mem.maybe_note_compiled(
                    'Model.train_chunk', jitted,
                    (st['params'], st['buffers'], st['opt'],
                     jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
                     jnp.zeros((), jnp.float32), *stacked),
                    source='hapi')
            self._train_chunk_cache[key] = jitted
            from ..analysis import note_retrace
            note_retrace('Model.train_chunk',
                         len(self._train_chunk_cache), instance=self)
        fn = self._train_chunk_cache[key]
        from ..core import rng as rng_mod
        seed = rng_mod.get_seed()
        if getattr(self, '_base_key_seed', None) != seed:
            self._base_key = jax.random.PRNGKey(seed)
            self._base_key_seed = seed
        if first_call:
            _ct0 = _time.perf_counter()
        new_params, new_buf, new_opt, new_step, losses, oks, mres = fn(
            st['params'], st['buffers'], st['opt'], self._base_key,
            jnp.asarray(st['step'], jnp.int32),
            jnp.asarray(self._optimizer.get_lr(), jnp.float32),
            *stacked)
        if first_call:
            from .. import telemetry
            _dt = _time.perf_counter() - _ct0
            telemetry.event('compile', name='Model.train_chunk',
                            dur_s=round(_dt, 6), fused_steps=k,
                            variants=len(self._train_chunk_cache))
            telemetry.add('compile.count')
            telemetry.add('compile.total_s', _dt)
        if self._check_finite_steps:
            # exact-skip contract at chunk cadence: ONE sanctioned
            # host sync materializes the K-step finite mask; skipped
            # steps advanced neither the counter nor (on device) the
            # state.  NanGuard reads _last_step_ok once per chunk, so
            # the chunk reduces CONSERVATIVELY: any poisoned step
            # marks the whole chunk not-ok — a mostly-NaN chunk whose
            # last step happens finite must still count a strike
            # (strike granularity becomes per-chunk; see MIGRATION)
            mask = _scan.chunk_sync(oks)
            n_ok = int(mask.sum())
            self._last_step_ok = bool(mask.all())
            st.update(params=new_params, buffers=new_buf, opt=new_opt,
                      step=st['step'] + n_ok)
            self._optimizer._global_step = st['step']
        else:
            # sync-free path: zero host reads per chunk — the device
            # step counter is adopted lazily and the mask stays a
            # device array for whoever chooses to pay the sync
            self._last_step_ok = oks[-1]
            st.update(params=new_params, buffers=new_buf, opt=new_opt,
                      step=new_step)
            self._optimizer._global_step = st['step']
        self._chunk_metric_update(mres)
        return losses, oks

    @staticmethod
    def _merge_chunk_dim(v):
        """(K, N, ...) stacked metric stats -> (K*N, ...): metric
        update() accumulates sums/counts, so feeding the chunk-merged
        stats once equals feeding K per-step stats (skipped steps were
        already masked to zero on device)."""
        if getattr(v, 'ndim', 0) >= 2:
            return v.reshape((-1,) + tuple(v.shape[2:]))
        return v

    def _chunk_metric_update(self, mres):
        logs = []
        for m, r in zip(self._metrics, mres):
            if isinstance(r, (tuple, list)):
                logs.append(m.update(*[self._merge_chunk_dim(x)
                                       for x in r]))
            else:
                logs.append(m.update(self._merge_chunk_dim(r)))
        return logs

    def _lint_train_step(self, n_in, st, arrays, fused=None):
        """prepare(lint=...): audit the exact step about to compile
        (jaxpr rules, donation included) + the forward's source —
        via safe_emit, so only LintError (the 'error'-mode verdict)
        escapes and analyzer crashes degrade to a warning.

        Under an ACTIVE mesh (distributed env) the audit escalates to
        the lowered-HLO pass: the step is lowered in hapi's SPMD
        posture — state replicated, batch sharded over the mesh's
        first data axis — and the post-partitioner rules
        (replicated-giant-hlo, collective-cost, resharding,
        peak-memory) extend the jaxpr report."""
        from .. import analysis
        from ..distributed import env as _env

        def build():
            step_fn = self._build_train_step(n_in)
            args = (st['params'], st['buffers'], st['opt'],
                    jax.random.PRNGKey(0), jnp.zeros((), jnp.int32),
                    jnp.zeros((), jnp.float32))
            report = analysis.lint(
                step_fn, *args, *arrays,
                donate_argnums=(0, 1, 2), source=False,
                fused_steps=fused, name='Model.train_step')
            mesh = _env.get_mesh()
            if mesh is not None:
                analysis.escalate_hlo(
                    report, step_fn, args, arrays, mesh,
                    donate_argnums=(0, 1, 2), name='Model.train_step')
            return report.extend(analysis.lint_layer(self.network))

        analysis.safe_emit(build, self._lint)

    def _eval_batch_lazy(self, arrays, n_in):
        """One compiled eval step with NO host readback: the returned
        loss is a device array and metric updates are lazy jnp adds
        (SURVEY §2#21 — a sync per batch stalls the dispatch
        pipeline)."""
        st = self._get_fstate() if self._optimizer is not None else None
        if st is None:
            params, buffers = self.network.functional_state()
        else:
            params, buffers = st['params'], st['buffers']
        key = self._batch_key(arrays, ('eval', n_in))
        first_call = key not in self._eval_step_cache
        if first_call:
            self._eval_step_cache[key] = self._make_eval_step(n_in)
        # eval runs layers in eval() mode (dropout off), but seed from
        # the user's paddle.seed anyway: a layer that samples in eval
        # must not silently pin to a hard-coded stream
        from ..core import rng as rng_mod
        if first_call:
            import time as _time
            _ct0 = _time.perf_counter()
        outs, loss, mres = self._eval_step_cache[key](
            params, buffers, jax.random.PRNGKey(rng_mod.get_seed()),
            *arrays)
        if first_call:
            from .. import telemetry
            _dt = _time.perf_counter() - _ct0
            telemetry.event('compile', name='Model.eval_batch',
                            dur_s=round(_dt, 6),
                            variants=len(self._eval_step_cache))
            telemetry.add('compile.count')
            telemetry.add('compile.total_s', _dt)
        for m, r in zip(self._metrics, mres):
            m.update(r) if not isinstance(r, (tuple, list)) \
                else m.update(*r)
        return outs, loss

    def eval_batch(self, inputs, labels=None):
        """One compiled eval step; returns (loss, outputs) as DEVICE
        arrays — the old ``float(loss)`` / ``np.asarray(o)`` here cost
        a device→host round trip per batch (host-sync lint).  Call
        ``float(loss)`` / ``np.asarray(o)`` at your log boundary to
        materialize."""
        batch = _as_list(inputs) + _as_list(labels)
        arrays, n_in = self._split_batch(batch)
        outs, loss = self._eval_batch_lazy(arrays, n_in)
        return loss, list(outs)

    def predict_batch(self, inputs):
        arrays = [_to_jnp(b) for b in _as_list(inputs)]
        n_in = len(arrays)
        if self._fstate is not None:
            params, buffers = self._fstate['params'], \
                self._fstate['buffers']
        else:
            params, buffers = self.network.functional_state()
        key = self._batch_key(arrays, ('pred', n_in))
        if key not in self._pred_step_cache:
            self._pred_step_cache[key] = self._make_pred_step(n_in)
        from ..core import rng as rng_mod
        outs = self._pred_step_cache[key](
            params, buffers, jax.random.PRNGKey(rng_mod.get_seed()),
            *arrays)
        return [np.asarray(o) for o in outs]

    # -- loops ---------------------------------------------------------------
    def _to_loader(self, data, batch_size, shuffle, num_workers,
                   drop_last=False):
        if data is None or isinstance(data, DataLoader):
            return data
        if isinstance(data, Dataset):
            return DataLoader(data, batch_size=batch_size, shuffle=shuffle,
                              num_workers=num_workers, drop_last=drop_last)
        return data  # any iterable of batches

    def fit(self, train_data=None, eval_data=None, batch_size=1, epochs=1,
            eval_freq=1, log_freq=10, save_dir=None, save_freq=1,
            verbose=2, drop_last=False, shuffle=True, num_workers=0,
            callbacks=None, profile=None, fused_steps=None):
        """``profile=`` enables sampled on-device trace capture over
        the train loop (telemetry.profile): None → the
        ``PADDLE_TPU_PROFILE`` env decides (default off), False forces
        off, True/str/dict/ProfileSchedule configure the windows.
        Trace artifacts land next to the flight-recorder dumps
        (``save_dir`` when given); each closed window emits a
        ``profile_capture`` event and the device-compute vs
        collective-time breakdown gauges.  Steps outside a window pay
        one integer compare — the sync-free loop contract holds.

        ``fused_steps=K`` compiles K train steps into ONE XLA module
        (core.scan_loop): batches are staged in K-step chunks
        (double-buffered device prefetch when ``num_workers>0``),
        losses/metrics accumulate on device inside the scan, and
        callbacks / logging / the preemption check run at chunk
        boundaries — dispatch overhead drops ~K-fold on small models.
        None defers to the ``PADDLE_TPU_FUSED_STEPS`` env (default
        off); K=1 is bit-exact with the per-step loop.  A short final
        chunk falls back to the per-step path."""
        assert self._optimizer is not None and self._loss is not None, \
            'call prepare(optimizer, loss) before fit'
        train_loader = self._to_loader(train_data, batch_size, shuffle,
                                       num_workers, drop_last=drop_last)
        eval_loader = self._to_loader(eval_data, batch_size, False,
                                      num_workers)
        steps = len(train_loader) if hasattr(train_loader, '__len__') \
            else None
        cbks = config_callbacks(
            callbacks, model=self, batch_size=batch_size, epochs=epochs,
            steps=steps, log_freq=log_freq, verbose=verbose,
            save_freq=save_freq, save_dir=save_dir,
            metrics=['loss'] + [m.name() for m in self._metrics])
        self.stop_training = False
        # preemption contract: SIGTERM during fit stops at the next
        # step boundary, ModelCheckpoint's on_train_end writes the
        # final checkpoint, and the tail of fit() exits
        # PREEMPTED_EXIT_CODE (SIGINT instead hands control back).
        # fit only BORROWS the handlers: if nothing else (launcher,
        # auto_checkpoint range) installed them, they are restored on
        # exit so a later Ctrl-C still kills the program normally
        from ..resilience import shutdown as _sd
        from .. import telemetry as _tel
        _owned_handlers = not _sd.handler_installed()
        _install_shutdown()
        try:
            with _tel.span('fit', epochs=epochs):
                self._fit_loop(cbks, train_loader, eval_loader, epochs,
                               eval_freq, batch_size, num_workers,
                               log_freq=log_freq, profile=profile,
                               save_dir=save_dir,
                               fused_steps=fused_steps)
        finally:
            requested = _sd.shutdown_requested()
            sig = _sd.preemption_signal()
            if _owned_handlers:
                _sd.uninstall_shutdown()
                if sig == _signal.SIGINT:
                    # user stop, and the latch is OURS: un-latch so
                    # the next fit starts fresh — on the exception
                    # path too, or a KeyboardInterrupt here would
                    # poison every later training loop.  A BORROWED
                    # latch is left set: the outer installer (e.g. an
                    # auto_checkpoint range wrapping this fit) still
                    # needs to see the request
                    _sd.clear_shutdown()
        if requested and sig != _signal.SIGINT:
            # preemption — SIGTERM or a programmatic request() from a
            # cluster agent: the final checkpoint just landed in
            # on_train_end, exit with the code the elastic supervisor
            # restarts for free.  SIGINT (user) instead returns
            # control with training cleanly stopped.  The flight
            # recorder lands NEXT TO that checkpoint so the preempted
            # worker is post-mortemable without live logs (the signal
            # handler already ring-buffered the preemption event; this
            # writes the durable copy inside the grace window).
            try:
                step = int(self._optimizer._global_step)
            except (TypeError, ValueError):
                step = -1
            _tel.event('preemption', signum=sig, where='hapi.fit',
                       step=step)
            dump_dir = save_dir or _tel.flight_dir()
            if dump_dir:
                _tel.dump_flight(os.path.join(
                    dump_dir, f'flightrec-{step}.json'))
            _sd.exit_if_requested()
        return self

    def _fit_loop(self, cbks, train_loader, eval_loader, epochs,
                  eval_freq, batch_size, num_workers, log_freq=10,
                  profile=None, save_dir=None, fused_steps=None):
        from .. import telemetry as _tel
        # sync-free telemetry: device loss scalars + host step/wait
        # times buffer in the accumulator and flush every
        # flush_interval steps (None when telemetry is not enabled)
        acc = _tel.step_accumulator('train')
        # sampled trace capture (telemetry.profile); None when off.
        # hapi steps carry no jit shardings, so windows yield the
        # profile_capture breakdown without the collective census
        # join — the mesh path (ParallelTrainer) does both.
        prof = _tel.step_profiler(profile, base_dir=save_dir,
                                  name='fit')
        # metric accumulate() is a device readback: pay it only on
        # steps some logger actually prints — the union of fit's
        # log_freq and every callback's own log_freq (a user
        # ProgBarLogger(log_freq=3) under fit(log_freq=10) must still
        # see metric values at ITS boundaries)
        log_freqs = {max(1, int(log_freq))}
        for cb in cbks:
            f = getattr(cb, 'log_freq', None)
            if isinstance(f, int) and f > 0:
                log_freqs.add(f)
        from ..core import scan_loop as _scan
        k = _scan.resolve_fused_steps(fused_steps)
        cbks.on_train_begin({})
        try:
            if k:
                self._fit_epochs_fused(
                    cbks, train_loader, eval_loader, epochs,
                    eval_freq, batch_size, num_workers, log_freqs,
                    acc, prof, k)
            else:
                self._fit_epochs(cbks, train_loader, eval_loader,
                                 epochs, eval_freq, batch_size,
                                 num_workers, log_freqs, acc, prof)
        finally:
            if prof is not None:
                # ALWAYS finalize — an exception mid-epoch must not
                # leave jax.profiler tracing for the rest of the
                # process (every later window would fail to start).
                # sync on the last loss so a still-open window waits
                # for its traced async steps before stop_trace.
                prof.close(sync=self._last_fit_loss)

    def _fit_epochs(self, cbks, train_loader, eval_loader, epochs,
                    eval_freq, batch_size, num_workers, log_freqs,
                    acc, prof):
        import time as _time
        _perf = _time.perf_counter
        gstep = 0
        self._last_fit_loss = None
        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch, {})
            for m in self._metrics:
                m.reset()
            logs = {}
            step = -1
            loader_it = iter(train_loader)
            while True:
                _tw0 = _perf()
                try:
                    batch = next(loader_it)
                except StopIteration:
                    break
                wait_s = _perf() - _tw0
                step += 1
                cbks.on_train_batch_begin(step, {})
                arrays, n_in = self._split_batch(batch)
                _ts0 = _perf()
                loss, _ = self.train_batch(arrays[:n_in], arrays[n_in:])
                self._last_fit_loss = loss
                if acc is not None:
                    acc.observe(step=step, step_time_s=_perf() - _ts0,
                                wait_s=wait_s, loss=loss)
                if prof is not None:
                    prof.observe(gstep, sync=loss)   # 0-based index
                gstep += 1
                logs = {'loss': loss}
                if any((step + 1) % f == 0 for f in log_freqs):
                    for m in self._metrics:
                        logs[str(m.name())] = m.accumulate()
                cbks.on_train_batch_end(step, logs)
                if _shutdown_requested():
                    # preemption (SIGTERM latched by GracefulShutdown):
                    # stop at this step boundary; on_train_end below
                    # runs ModelCheckpoint's final save — the "final
                    # synchronous checkpoint" of the preemption
                    # contract — and the caller's exit_if_requested()
                    # turns it into PREEMPTED_EXIT_CODE
                    self.stop_training = True
                if self.stop_training:
                    break
            if acc is not None:
                acc.flush()
            for m in self._metrics:
                logs[str(m.name())] = m.accumulate()
            cbks.on_epoch_end(epoch, logs)
            if self.stop_training:
                # preemption/early-stop: every second of the grace
                # window belongs to the final checkpoint, not to an
                # eval pass
                break
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(
                    eval_loader, batch_size=batch_size, verbose=0,
                    num_workers=num_workers, _callbacks=cbks)
                cbks.on_eval_end(eval_logs)
            if self.stop_training:
                break
        cbks.on_train_end(logs)
        self._sync_back()

    def _fit_epochs_fused(self, cbks, train_loader, eval_loader,
                          epochs, eval_freq, batch_size, num_workers,
                          log_freqs, acc, prof, k):
        """The K-step fused epoch loop (core.scan_loop): batches are
        staged in K-chunks — stacked + device-put on a background
        thread when the loader has workers, so chunk N+1's transfer
        overlaps chunk N's execution — and each chunk is ONE compiled
        dispatch.  Callbacks, logging and the preemption check run at
        chunk boundaries; a short final chunk takes the per-step
        path.  Losses stay device arrays throughout (the
        accumulator's chunk rows expand to per-step stats at flush)."""
        import time as _time
        from ..core import scan_loop as _scan
        from .. import telemetry as _tel
        _perf = _time.perf_counter
        gstep = 0
        self._last_fit_loss = None

        def stage(batches):
            # keep leaves RAW (numpy stays host, Tensors unwrap to
            # their device values): stack_batches then pays one
            # transfer per host field and zero readbacks for device
            # fields — no _to_jnp round-trip before stacking
            rows = [[v.value if isinstance(v, Tensor) else v
                     for v in _as_list(b)] for b in batches]
            return (_scan.stack_batches(rows),
                    self._split_arity(len(rows[0])))

        for epoch in range(epochs):
            cbks.on_epoch_begin(epoch, {})
            for m in self._metrics:
                m.reset()
            logs = {}
            step = -1
            # overlap decision follows the LOADER's own workers (a
            # pre-built DataLoader(num_workers=4) must get background
            # staging even when fit's num_workers default is 0)
            loader_workers = getattr(train_loader, 'num_workers',
                                     None)
            if loader_workers is None:
                loader_workers = num_workers
            pref = _scan.ChunkPrefetcher(
                iter(train_loader), k, stage,
                background=loader_workers > 0)
            for staged, n, wait_s in pref:
                if n == k:
                    (stacked, n_in) = staged
                    cbks.on_train_batch_begin(step + 1, {})
                    _ts0 = _perf()
                    losses, _oks = self.train_chunk(stacked, n_in, k)
                    dt = _perf() - _ts0
                    loss = losses[-1]
                    self._last_fit_loss = loss
                    if acc is not None:
                        acc.observe_chunk(step + 1, n, step_time_s=dt,
                                          wait_s=wait_s, loss=losses)
                    _tel.set_gauge('fused.host_wait_ms',
                                   round(wait_s * 1000.0, 4))
                    if prof is not None:
                        prof.observe(gstep, sync=loss, span=n)
                    gstep += n
                    step += n
                    logs = {'loss': loss}
                    if any((step + 1 - j) % f == 0
                           for f in log_freqs for j in range(n)):
                        for m in self._metrics:
                            logs[str(m.name())] = m.accumulate()
                    cbks.on_train_batch_end(step, logs)
                else:
                    # ragged tail: run the < K remaining batches
                    # through the per-step module instead of paying a
                    # one-off K'-length compile
                    for batch in staged:
                        step += 1
                        cbks.on_train_batch_begin(step, {})
                        arrays, n_in = self._split_batch(batch)
                        _ts0 = _perf()
                        loss, _ = self.train_batch(arrays[:n_in],
                                                   arrays[n_in:])
                        self._last_fit_loss = loss
                        if acc is not None:
                            acc.observe(step=step,
                                        step_time_s=_perf() - _ts0,
                                        loss=loss)
                        if prof is not None:
                            prof.observe(gstep, sync=loss)
                        gstep += 1
                        logs = {'loss': loss}
                        if any((step + 1) % f == 0 for f in log_freqs):
                            for m in self._metrics:
                                logs[str(m.name())] = m.accumulate()
                        cbks.on_train_batch_end(step, logs)
                if _shutdown_requested():
                    # preemption lands at the chunk boundary we are on:
                    # fused granularity is K steps, and the state here
                    # IS a chunk boundary — the final checkpoint in
                    # on_train_end restores to exactly this step
                    self.stop_training = True
                if self.stop_training:
                    break
            if acc is not None:
                acc.flush()
            for m in self._metrics:
                logs[str(m.name())] = m.accumulate()
            cbks.on_epoch_end(epoch, logs)
            if self.stop_training:
                break
            if eval_loader is not None and (epoch + 1) % eval_freq == 0:
                eval_logs = self.evaluate(
                    eval_loader, batch_size=batch_size, verbose=0,
                    num_workers=num_workers, _callbacks=cbks)
                cbks.on_eval_end(eval_logs)
            if self.stop_training:
                break
        cbks.on_train_end(logs)
        self._sync_back()

    def evaluate(self, eval_data, batch_size=1, log_freq=10, verbose=2,
                 num_workers=0, callbacks=None, _callbacks=None):
        loader = self._to_loader(eval_data, batch_size, False, num_workers)
        for m in self._metrics:
            m.reset()
        total_loss, n_batches = 0.0, 0
        cbks = _callbacks
        if cbks is None:
            cbks = config_callbacks(
                callbacks, model=self, batch_size=batch_size,
                steps=len(loader) if hasattr(loader, '__len__') else None,
                log_freq=log_freq, verbose=verbose, mode='eval',
                metrics=['loss'] + [m.name() for m in self._metrics])
            cbks.on_eval_begin({})
        from .. import telemetry as _tel
        with _tel.span('evaluate'):
            for step, batch in enumerate(loader):
                arrays, n_in = self._split_batch(batch)
                # lazy path: the loss stays a device array and the
                # metric updates are jnp adds — zero per-batch host
                # syncs; a callback that formats the loss pays the
                # sync itself, and only when it actually logs
                _, loss = self._eval_batch_lazy(arrays, n_in)
                total_loss = total_loss + loss
                n_batches += 1
                cbks.on_eval_batch_end(step, {'loss': loss})
        logs = {'loss': float(total_loss) / max(1, n_batches)}
        for m in self._metrics:
            logs[str(m.name())] = m.accumulate()
        if _callbacks is None:
            cbks.on_eval_end(logs)
        return logs

    def predict(self, test_data, batch_size=1, num_workers=0,
                stack_outputs=False, callbacks=None, verbose=1):
        loader = self._to_loader(test_data, batch_size, False, num_workers)
        outputs = []
        for batch in loader:
            arrays, n_in = self._split_batch(batch)
            outs = self.predict_batch(arrays[:n_in])
            outputs.append(outs)
        # transpose: list-of-batches -> per-output lists
        n_out = len(outputs[0]) if outputs else 0
        per_out = [[b[i] for b in outputs] for i in range(n_out)]
        if stack_outputs:
            per_out = [np.concatenate(o, axis=0) for o in per_out]
        return per_out

    # -- persistence ---------------------------------------------------------
    def save(self, path, training=True):
        self._sync_back()
        if training:
            d = os.path.dirname(path)
            if d:
                os.makedirs(d, exist_ok=True)
            _save(self.network.state_dict(), path + '.pdparams')
            if self._optimizer is not None:
                _save(self._optimizer.state_dict(), path + '.pdopt')
        else:
            from .. import jit as _jit
            _jit.save(self.network, path)

    def load(self, path, skip_mismatch=False, reset_optimizer=False):
        sd = _load(path + '.pdparams')
        try:
            self.network.set_state_dict(sd)
        except (KeyError, ValueError):
            if not skip_mismatch:
                raise
            warnings.warn('skip_mismatch=True: partially loaded')
        if not reset_optimizer and self._optimizer is not None and \
                os.path.exists(path + '.pdopt'):
            self._optimizer.set_state_dict(_load(path + '.pdopt'))
        self._invalidate()
        return self

    def summary(self, input_size=None, dtype=None):
        from .summary import summary as _summary
        return _summary(self.network, input_size, dtypes=dtype)
