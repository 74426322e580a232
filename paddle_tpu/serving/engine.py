"""Serving engine: continuous batching over a per-sequence cache: the
paged KV pool; for a model that mixes full and window attention layers
on grouped query heads (`model.cache_spec()`) a `LayerGroupKVCache`,
two groups of layers with a pool and a table each; or for a model
whose memory is one recurrent state a sequence (`model.serving_state
== 'recurrent'`) a `RecurrentStateCache`.  The model says which; the
scheduler, the modules' scaffolding, `step`, `run` and `warmup` are
one code path over all three.

Ties the whole PR-7..11 runway into live decode throughput:

- **paged KV cache** (``kv_cache.py``): fixed-size blocks in one
  preallocated pool, per-sequence block tables, heads sharded on the
  ``tp`` mesh axis;
- **ragged paged attention** (``ops/paged_attention.py``): the whole
  live set — every sequence at its own depth — decodes as ONE batched
  step.  On the chip a Pallas kernel reads each sequence's blocks in
  place, up to its length (within 1e-5 of the reference; a row never
  depends on its batch); everywhere else the gather-and-attend
  reference runs, bit-exact vs the dense cached path.  ``report()``
  says which (``paged_kernel``) and what share of the tables' blocks
  the dispatches had to read (``kv_read_share``);
- **continuous batching** (``scheduler.py``): admit/evict at every
  intervention, prefill into freed blocks, immediate backfill;
- **a decode dispatch in flight**: the span of intervention N+1 is
  planned by counts and sent while N is still running on the device,
  and the host reads, absorbs and books N behind it.  An intervention
  is ``deadlines → admit → prefill_dispatch → reserve → plan →
  decode_dispatch`` (N+1) ``→ decode_sync`` (N) ``→ absorb`` (N) ``→
  first_token_sync`` (of the prefills just sent) ``→ bookkeeping``.
  A row's last token stays on the device (``_last``, one entry a
  request's ``slot``): the prefill modules write their first tokens
  into it, the decode modules read a row's input token from it and
  write the span's last one back, and the host reads tokens only to
  deliver them, never to schedule.  The scheduler plans from ``ctx +
  dispatched`` (``scheduler.py``'s docstring): a row that ends by
  ``max_new_tokens`` is known to end in N before N has run, so its
  slot and blocks go to the next admission when N+1 is planned, and
  occupancy does not fall; the device runs what it is sent in order,
  so a block freed on the host while N is in flight is never
  rewritten before N has read it.  A row that ends on ``eos_id``
  cannot be foreseen: the token it left on the device silences it in
  N+1, the host learns of it when it absorbs N, and its slot and
  blocks come back ONE SPAN LATE.  A caller who steps the engine by
  hand reads a span's tokens one ``step()`` after it was sent;
  ``drain()`` reads what is in flight without sending more (``run()``
  at its timeout and at its end, ``cancel()`` and ``warmup()`` call
  it).  Where requests may still arrive, ``run()`` and
  ``ServingFrontend`` take the next intervention only when the span
  in flight is about to end (``wait_s``: the engine's own measured
  lengths, no option), so an arrival waits no longer than when each
  span was planned after the one before it had been read;
  ``counts()['decode_dispatches_ahead']`` and the ``ahead`` field of
  ``serve_step`` say how often a dispatch was sent ahead;
- **fused multi-step decode**: ``decode_span=K`` scans K decode steps
  inside one compiled module between scheduler interventions — the
  ROADMAP item-4 remainder lifted to the decode loop;
- **finite module set**: prompts bucket to the declared pow2 prompt
  set, the live batch pads to the declared pow2 batch set, admission
  bursts chunk to pow2 prefill batches — the whole serving surface is
  ``len(prompt_buckets) x len(prefill chunks) + len(batch_buckets)``
  compiled modules, built deterministically by ``warmup()`` and
  AOT-compiled by ``tools/precompile.py --serve`` (zero cold-start
  compiles), audited by ``check_ckpt --deep`` like any other
  precompile entry;
- **per-request SLOs**: watchdog-derived deadline budgets (PR 10)
  evict starved requests with a ``timeout`` telemetry event; TTFT /
  TPOT land on ``serve_request`` events and PR-8 profile windows
  attribute device time to exact intervention ids;
- **live observability** (``serve_metrics_port=`` /
  ``PADDLE_TPU_METRICS_PORT``, default OFF): a
  ``telemetry.live.LiveAggregator`` subscribed to the recorder
  stream keeps rolling TTFT/TPOT/occupancy windows, SLO/drift
  monitors emit ``slo_breach``/``drift_detected``, and a stdlib HTTP
  server exposes ``/healthz`` ``/status.json`` ``/metrics``
  ``/requests/<rid>`` — scrapes read host-side rolling state only,
  so a live scrape changes no numerics and adds no syncs (pinned by
  tests/test_event_live.py); every request carries a full
  lifecycle trace (``serve_trace`` events).

The decode math runs through the SAME ``GPTForCausalLM.prefill`` /
``decode_step`` functional forwards that ``generate()`` uses, so on
the reference path greedy engine output is bit-exact with sequential
batch-1 generate — pinned by tests/test_engine_serving.py.
"""
import collections
import json
import math
import statistics
import time
import zlib

import numpy as np

from .. import nn
from ..core import compile_cache as _cc
from ..resilience.watchdog import resolve_watchdog
from .kv_cache import LayerGroupKVCache, PagedKVCache, PagedCacheView, \
    RecurrentStateCache, blocks_for
from .scheduler import ContinuousBatchingScheduler, Request, \
    RejectedRequest

__all__ = ['ServeConfig', 'ServingEngine', 'DecodeAuditLayer',
           'request_seed']


def request_seed(rid, engine_seed):
    """The per-request sampling base seed: a pure function of (rid,
    engine seed), so ANY engine sharing the config seed — including a
    surviving replica replaying a dead replica's request — derives the
    identical seed and continues the identical token stream (the
    ops/sampling per-position key discipline does the rest)."""
    return (zlib.crc32(str(rid).encode()) ^ int(engine_seed)) \
        & 0x7FFFFFFF


def _pow2_chain(lo, hi):
    out = []
    b = int(lo)
    while b < int(hi):
        out.append(b)
        b *= 2
    out.append(int(hi))
    return tuple(sorted(set(out)))


class ServeConfig:
    """Declared serving surface — every field below shapes the finite
    compiled-module set, so the config IS the AOT bucket declaration.
    """

    def __init__(self, *, block_size=16, max_slots=8, decode_span=4,
                 prompt_buckets=None, batch_buckets=None,
                 prefill_batch=8, max_model_len=None, temperature=0.0,
                 top_k=None, eos_id=None, num_blocks=None,
                 request_deadline_s=None, watchdog=None, profile=None,
                 seed=0, quantize=None):
        self.block_size = int(block_size)
        self.max_slots = int(max_slots)
        self.decode_span = max(1, int(decode_span))
        # admission bursts prefill together: chunks of up to
        # `prefill_batch` same-bucket prompts share ONE dispatch
        # (modules per (prompt bucket, pow2 chunk) pair)
        self.prefill_batch = max(1, int(prefill_batch))
        self.prompt_buckets = None if prompt_buckets is None \
            else tuple(sorted(set(int(p) for p in prompt_buckets)))
        self.batch_buckets = batch_buckets
        self.max_model_len = max_model_len
        self.temperature = temperature
        self.top_k = top_k
        self.eos_id = eos_id
        self.num_blocks = num_blocks
        self.request_deadline_s = request_deadline_s
        self.watchdog = watchdog
        self.profile = profile
        self.seed = int(seed)
        # weight-only PTQ of the served model: None (full width),
        # 'int8' (Int8DynamicLinear) or 'int4' (packed nibbles) —
        # decode reads half-/quarter-width weights from HBM.  Part of
        # signature(), so quantized and full-width surfaces can never
        # share a compiled module.
        if quantize not in (None, 'int8', 'int4'):
            raise ValueError(f'ServeConfig quantize={quantize!r}: '
                             "expected None, 'int8' or 'int4'")
        self.quantize = quantize

    @classmethod
    def from_json(cls, path_or_dict):
        """A serving config file: the ServeConfig fields, plus
        ``model``/``model_kwargs`` keys the callers that build models
        from configs (tools/precompile.py --serve) consume."""
        if isinstance(path_or_dict, dict):
            doc = dict(path_or_dict)
        else:
            with open(path_or_dict) as f:
                doc = json.load(f)
        doc.pop('model', None)
        doc.pop('model_kwargs', None)
        return cls(**doc)

    def resolved(self, model_config):
        """Fill derived fields from the model config; returns self."""
        if self.max_model_len is None:
            self.max_model_len = int(model_config.max_seq_len)
        if self.prompt_buckets is None:
            hi = _cc.bucket_pow2(max(1, self.max_model_len // 2))
            self.prompt_buckets = _pow2_chain(min(8, hi), hi)
        if self.batch_buckets is None:
            self.batch_buckets = _pow2_chain(1, self.max_slots)
        else:
            self.batch_buckets = tuple(sorted(set(
                int(b) for b in self.batch_buckets)))
        if self.num_blocks is None:
            per_seq = blocks_for(self.max_model_len, self.block_size)
            self.num_blocks = self.max_slots * per_seq + 1
        if max(self.prompt_buckets) > self.max_model_len:
            raise ValueError(
                f'prompt bucket {max(self.prompt_buckets)} exceeds '
                f'max_model_len {self.max_model_len}')
        return self

    def signature(self):
        """The scalar fields that key compiled serving modules."""
        return tuple(sorted(
            (k, v if not isinstance(v, (list, tuple)) else tuple(v))
            for k, v in vars(self).items()
            if k not in ('watchdog', 'profile', 'request_deadline_s')))

    def to_dict(self):
        return {k: (list(v) if isinstance(v, tuple) else v)
                for k, v in vars(self).items()
                if k not in ('watchdog', 'profile')}


class ServingEngine:
    """Continuous-batching decode over one causal LM with ``prefill``
    and ``decode_step`` (``GPTForCausalLM``, ``RetentionForCausalLM``).

    ::

        eng = ServingEngine(model, ServeConfig(max_slots=64))
        eng.submit(prompt_ids, max_new_tokens=64)
        report = eng.run()          # drain; per-request TTFT/TPOT

    Of routed-expert models it serves the DROPLESS ones (every
    token's experts compute it, at every batch: `models/routed_window`)
    and refuses `incubate/moe.py::SwitchMoE` (`config.moe_num_experts`),
    whose experts have a capacity: the pad rows of a prefill bucket
    and of a batch bucket would contend for it with the true rows, and
    a row's tokens would depend on its batch — same exemption as
    generate's pow2 bucketing.
    """

    def __init__(self, model, config=None, now_fn=time.monotonic,
                 serve_metrics_port=None, live_window_s=60.0):
        cfg = model.config
        if getattr(cfg, 'moe_num_experts', 0) > 0:
            raise ValueError(
                'the serving engine refuses a capacity-dropping MoE '
                '(SwitchMoE, config.moe_num_experts > 0): the pad rows '
                'of a bucket would contend with true rows for expert '
                'capacity (see GPTForCausalLM._decode_bucket); a '
                'dropless routed model serves (models/routed_window)')
        model.eval()
        self.model = model
        self.config = (config or ServeConfig()).resolved(cfg)
        applied = getattr(model, '_ptq_mode', None)
        if applied != (self.config.quantize or None):
            if applied is not None:
                # the swap dropped the float weights — an engine whose
                # declared signature disagrees with the model's actual
                # numerics would mis-key its compiled/AOT surface
                raise ValueError(
                    f'model was already PTQ-quantized ({applied!r}) '
                    f'but this config declares '
                    f'quantize={self.config.quantize!r}; build each '
                    'quantization mode from a FRESH model '
                    '(quantize_for_serving swaps weights in place)')
            # weight-only PTQ BEFORE functional_state: the swapped
            # Int8/Int4DynamicLinears' int8 buffers become the params/
            # buffers every prefill/decode module closes over, so the
            # whole compiled serving surface reads narrow weights from
            # HBM (and precompile --serve AOT-compiles the same —
            # quantize is part of the config signature)
            from ..quantization import quantize_for_serving
            quantize_for_serving(model, self.config.quantize)
        self.now_fn = now_fn
        # one engine-relative clock for EVERY timestamp (arrivals,
        # TTFT, deadlines) so offsets and wall reads never mix frames
        self._epoch = now_fn()
        self._clock = lambda: self.now_fn() - self._epoch
        self._params, self._buffers = model.functional_state()
        # the cache follows from what the model says it needs
        self.recurrent = getattr(model, 'serving_state',
                                 'paged') == 'recurrent'
        if self.recurrent:
            self.cache = RecurrentStateCache(
                **model.state_spec(), slots=self.config.max_slots,
                max_model_len=self.config.max_model_len)
        elif hasattr(model, 'cache_spec'):
            # grouped query heads and layers of two kinds: the cache's
            # shape is the model's to say (hidden_size // num_heads is
            # no head size there)
            self.cache = LayerGroupKVCache(
                **model.cache_spec(), block_size=self.config.block_size,
                num_blocks=self.config.num_blocks,
                max_slots=self.config.max_slots,
                decode_span=self.config.decode_span)
        else:
            nh = cfg.num_heads
            hd = cfg.hidden_size // nh
            self.cache = PagedKVCache(
                cfg.num_layers, nh, hd,
                block_size=self.config.block_size,
                num_blocks=self.config.num_blocks)
        self.scheduler = ContinuousBatchingScheduler(
            self.cache, max_slots=self.config.max_slots,
            batch_buckets=self.config.batch_buckets,
            bucket_fn=self.prompt_bucket,
            max_model_len=self.config.max_model_len,
            decode_span=self.config.decode_span,
            eos_id=self.config.eos_id, now_fn=self._clock)
        self.budget = resolve_watchdog(self.config.watchdog)
        self._modules = {}
        self.compile_count = 0
        self.interventions = 0
        self.decoded_tokens = 0
        # each live row's last token, on the device, at the request's
        # `slot` (the entry past the last is the padding rows'): the
        # prefill modules write it, the decode modules read and write
        # it, and the host reads tokens only to deliver them
        import jax.numpy as jnp
        self._last = jnp.zeros((self.config.max_slots + 1,), jnp.int64)
        # the decode dispatch that was sent and not yet read (None, or
        # a dict: plan, the device arrays, when it was sent, whether it
        # was sent ahead of an unread one), and how many were
        self._in_flight = None
        self.decode_dispatches_ahead = 0
        # the engine's own measurements, on the machine's clock: when
        # the device last finished something the host waited for, the
        # last lengths of a decode dispatch by its shape and of the
        # host's planning (the start of an intervention to its decode
        # dispatch).  `wait_s` reckons from them what is left of the
        # span in flight
        self._device_free_t = 0.0
        self._span_s = {}
        self._plan_s = collections.deque(maxlen=3)
        self._rid = 0
        self._prefills = 0
        # first-token / rollback counts carried to the NEXT serve_step
        # event so the live plane's token accounting matches
        # decoded_tokens exactly (prefill-only interventions emit no
        # serve_step of their own)
        self._pending_prefilled = 0
        self._pending_discarded = 0
        # KV blocks the decode dispatches had to read against the
        # blocks their tables hold (DecodePlan.kv_blocks, summed over
        # token steps), and the paths the decode modules were built on
        self.kv_blocks_read = 0
        self.kv_blocks_table = 0
        self._decode_paths = set()
        # a recurrent cache: live rows x token steps (each rewrites one
        # slot of every layer's state)
        self.state_rows_updated = 0
        # prompt positions the prefill dispatches computed: the true
        # ones, and the bucket's of every row of the chunk (counted,
        # like `_prefills`, when a chunk's first tokens are read)
        self.prefill_tokens = 0
        self.prefill_padded_tokens = 0
        # prefill dispatches whose module took a routed model's Pallas
        # grouped matmul (`model.prefill_path`, asked when the module
        # is built), and the modules that did
        self.moe_kernel_prefills = 0
        self._kernel_prefills = set()
        # small integer counts a model's decode steps hand back through
        # their cache views (`model.step_stat_names`; a routed model's
        # assignments, experts hit and largest load), summed here
        self.step_stat_names = tuple(getattr(model, 'step_stat_names',
                                             ()))
        self.step_stats = np.zeros((len(self.step_stat_names),), np.int64)
        # the tensors such a model's tapped layers handed out in the
        # last decode dispatch, [span, ...], left on the device
        self.step_taps = None
        from ..telemetry.profile import step_profiler
        self._prof = step_profiler(profile=self.config.profile,
                                   name='serve')
        # -- live observability plane (default OFF; see telemetry.live) --
        # the aggregator consumes the recorder's boundary-rate stream,
        # the monitors turn its windows into slo_breach/drift_detected
        # events, and the HTTP server exposes /metrics + /status.json.
        # Nothing here adds device syncs: scrapes read host-side
        # rolling state only.
        self.live = None
        self.monitors = []
        self.metrics_server = None
        from ..telemetry.httpd import resolve_metrics_port
        port = resolve_metrics_port(serve_metrics_port)
        if port is not None:
            from ..telemetry.live import LiveAggregator
            from ..telemetry.monitors import DriftMonitor, SLOMonitor
            from ..telemetry.httpd import MetricsServer
            self.live = LiveAggregator(
                window_s=live_window_s).install()
            self.live.live_trace_fn = self._live_trace
            # watchdog budgets feed the SLO thresholds: the same
            # Budget that derives per-request deadlines defines the
            # aggregate TTFT envelope
            self.monitors = [
                self.live.attach_monitor(SLOMonitor(budget=self.budget)),
                self.live.attach_monitor(DriftMonitor()),
            ]
            # memory-pressure sensing rides the same plane when the
            # PADDLE_TPU_MEMSTATS grammar declares a budget_gb
            from ..telemetry import memory as _mem
            mcfg = _mem.resolve_memstats()
            if mcfg is not None and mcfg.budget_bytes is not None:
                from ..telemetry.monitors import MemoryMonitor
                self.monitors.append(self.live.attach_monitor(
                    MemoryMonitor(config=mcfg)))
        # live memory sampler: default OFF, armed by the same env
        # (idempotent no-op when unset; daemon thread, boundary rate)
        from ..telemetry import memory as _mem_sampler
        _mem_sampler.ensure_sampler()
        if port is not None:
            try:
                self.metrics_server = MetricsServer(self.live,
                                                    port=port).start()
            except Exception:
                # a dead port (EADDRINUSE, ...) must not leak the
                # recorder subscription: the engine never constructs,
                # so close() could never run
                self.live.uninstall()
                self.live = None
                self.monitors = []
                raise

    # -- live plane ----------------------------------------------------------
    def _live_trace(self, rid):
        """telemetry.live hook: the in-flight trace for `rid` (the
        finished ones live in the aggregator's serve_trace store).
        Runs on a scrape thread while the engine thread mutates the
        scheduler structures — copying a deque mid-mutation raises
        RuntimeError, so retry a few times and give up with None (the
        next scrape sees a settled state)."""
        sched = self.scheduler
        for _ in range(4):
            try:
                reqs = list(sched.running) + list(sched.queue) \
                    + list(sched.finished)
                for req in reqs:
                    if req.rid == rid:
                        return [dict(row) for row in req.trace]
                return None
            except RuntimeError:    # mutated during iteration
                continue
        return None

    def close(self):
        """Tear down the live plane (HTTP server + stream
        subscription).  Idempotent; the engine itself stays usable."""
        if self.metrics_server is not None:
            self.metrics_server.stop()
            self.metrics_server = None
        if self.live is not None:
            self.live.uninstall()

    # -- buckets -------------------------------------------------------------
    def prompt_bucket(self, t0):
        for b in self.config.prompt_buckets:
            if b >= t0:
                return b
        raise ValueError(
            f'prompt length {t0} exceeds the declared bucket set '
            f'{self.config.prompt_buckets}')

    def request_deadline_s(self, max_new_tokens):
        """Per-request completion budget: explicit config wins; an
        armed watchdog Budget (PR 10) derives prefill + per-span
        allowances; None = no deadline."""
        if self.config.request_deadline_s is not None:
            return float(self.config.request_deadline_s)
        if self.budget is None:
            return None
        return self.budget.request_budget_s(
            max_new_tokens, span=self.config.decode_span)

    # -- sampling (the shared ops/sampling discipline) -----------------------
    def _sample_fn(self):
        """``sample(logits[B, V], seeds[B], pos[B]) -> [B]``: each row
        draws with ``row_key(PRNGKey(seed), pos, 0)`` — the SAME key a
        batch-1 ``generate(seed=seed)`` would use at that absolute
        position, which is what makes sampled engine-vs-generate
        parity and mid-stream retry replay bit-exact (greedy ignores
        seeds/pos entirely)."""
        import jax
        from ..ops.sampling import make_row_sampler
        row_sample = make_row_sampler(self.config.temperature,
                                      self.config.top_k)

        @jax.named_scope('serve.sample')
        def sample(logits, seeds, pos):
            bases = jax.vmap(jax.random.PRNGKey)(seeds)
            return row_sample(logits, bases, pos)

        return sample

    # -- compiled modules ----------------------------------------------------
    def _fingerprint(self, kind, **extra):
        pspec = tuple(sorted((n, tuple(v.shape), str(v.dtype))
                             for n, v in self._params.items()))
        import jax.numpy as jnp
        return _cc.fingerprint(
            kind, config=tuple(sorted(vars(self.model.config).items())),
            serve=self.config.signature(), params=pspec,
            ids_dtype=str(jnp.asarray(0, jnp.int64).dtype), **extra)

    def _get_module(self, sig, build_fn, fp, example, name,
                    donate=()):
        mod = self._modules.get(sig)
        if mod is not None:
            return mod
        import jax
        # through_cache, not export-primary: the COLD path must keep
        # its donate_argnums — the pools are the whole KV cache and a
        # non-donating step memcpys them every call (a warm-start's
        # deserialized module forgoes donation, the documented PR-7
        # trade)
        jitted = _cc.through_cache(
            jax.jit(build_fn, donate_argnums=donate), example,
            fp=fp, name=name)
        self._modules[sig] = jitted
        self.compile_count += 1
        # memory observatory, armed-only (an extra lower+compile per
        # module): every serving module's XLA memory_analysis vs the
        # liveness prediction — through a FRESH jit, because a
        # warm-started exported call cannot re-lower
        from ..telemetry import memory as _mem
        if _mem.armed():
            _mem.maybe_note_compiled(name, jax.jit(build_fn), example,
                                     source='serving')
        return jitted

    def _prefill_build(self, P, B):
        """The prefill module body for one (prompt bucket, chunk)
        pair: ONE cached forward over B padded prompts, per-row first
        tokens sampled at each row's true length, every row's cache
        written where `where` says (the paged pool: its block-rounded
        KV through its own block-table row; a recurrent cache: its
        final state into its slot)."""
        import jax
        import jax.numpy as jnp
        model = self.model
        cache = self.cache
        sample = self._sample_fn()

        @jax.named_scope('serve.prefill')
        def prefill_fn(params, buffers, ids, t0, first, second, last,
                       where, slot, seeds):
            # the cache's arrays are a pair of per-layer tuples (k and
            # v pools; S and z states), two arguments as the paged
            # modules always had them
            caches = cache.prefill_caches(model, B, P, t0)
            logits, caches = model.prefill(
                params, buffers, ids, jnp.zeros((), jnp.int32), caches)
            lg = logits.value if hasattr(logits, 'value') else logits
            if lg.shape[1] == 1 and P > 1:
                # a model may return the last true position's logits
                # alone (a large head over every prompt position is
                # most of a prefill)
                rows = lg[:, 0]
            else:
                rows = jnp.take_along_axis(
                    lg, (t0 - 1)[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0]                      # [B, V]
            # the first token's absolute position is t0-1 — the same
            # position generate's prefill samples at
            tok = sample(rows, seeds,
                         (t0 - 1).astype(jnp.int64))  # [B]
            first, second = cache.store_prefill((first, second), caches,
                                                where)
            # the rows' first tokens stay on the device, where the
            # next decode span reads them (a padding row's slot is the
            # spare entry)
            last = last.at[slot].set(tok.astype(last.dtype))
            return tok, first, second, last

        return prefill_fn

    def _prefill_spec(self, P, B):
        """ONE source of truth for a prefill module's (fn, fp,
        example args, name, donate) — _prefill_module compiles it,
        precompile() AOT-exports it; they can never drift apart."""
        import jax.numpy as jnp
        fn = self._prefill_build(P, B)
        nblk = blocks_for(P, self.config.block_size)
        # keys= marks the per-request-position sampling discipline:
        # the module signature changed from one batch PRNGKey to
        # per-row seeds, and _fingerprint does not hash example avals
        # — without the marker a pre-discipline AOT artifact would
        # deserialize against the new call signature; layout= marks
        # the order of the cache's arrays for the same reason
        # tokens= marks the device-resident last-token vector, one
        # more donated argument, for the same reason
        fp = self._fingerprint('serve-prefill', bucket=P, nblk=nblk,
                               chunk=B, keys='per-request-pos',
                               layout=self.cache.layout_key,
                               tokens='device-slots')
        example = (self._params, self._buffers,
                   jnp.zeros((B, P), jnp.int64),
                   jnp.full((B,), P, jnp.int32), *self.cache.arrays(),
                   self._last,
                   jnp.asarray(self.cache.prefill_where((), B, P)),
                   jnp.full((B,), self.config.max_slots, jnp.int32),
                   jnp.zeros((B,), jnp.int64))
        return fn, fp, example, f'serve.prefill[{P}x{B}]', (4, 5, 6)

    def _prefill_module(self, P, B):
        sig = ('prefill', P, B)
        if sig in self._modules:
            return self._modules[sig]
        path = getattr(self.model, 'prefill_path', None)
        if path is not None and path(B, P) == 'kernel':
            self._kernel_prefills.add(sig)
        return self._get_module(sig, *self._prefill_spec(P, B))

    def _decode_build(self, S, K):
        """The fused decode module body for one (batch bucket, span):
        ``lax.scan`` over K single-token steps of the WHOLE live set —
        scheduler interventions only happen between these modules."""
        import jax
        import jax.numpy as jnp
        model = self.model
        cache = self.cache
        sample = self._sample_fn()
        eos = self.config.eos_id
        with_stats = bool(getattr(model, 'step_stat_names', ()))

        @jax.named_scope('serve.decode')
        def decode_fn(params, buffers, first, second, last, where, ctx,
                      slot, active, limit, seeds):
            arrays = cache.constrain((first, second))
            # a row's input token is what the prefill or the span
            # before this one left at its slot: the host plans by
            # counts and never sends a token
            tok = last[slot]
            if eos is not None:
                # the host cannot foresee a row that ended on EOS in
                # the span before this one (or at its prefill): the
                # token it left says so
                active = active & (tok != eos)

            def body(carry, _):
                tok, ctx, active, arrays = carry
                views = cache.decode_views(arrays, where, ctx, active)
                logits, views = model.decode_step(
                    params, buffers, tok[:, None], ctx, views)
                lg = logits.value if hasattr(logits, 'value') else logits
                # each row samples at its OWN absolute position (the
                # input token's slot, = generate's scan carry p) with
                # its OWN request seed — scheduling history and batch
                # composition cannot perturb the stream
                ntok = sample(lg[:, -1], seeds, ctx)
                emitted_valid = active
                ntok = jnp.where(active, ntok, tok)
                nctx = ctx + active.astype(ctx.dtype)
                nactive = active & (nctx < limit)
                if eos is not None:
                    nactive = nactive & (ntok != eos)
                out = (ntok, emitted_valid)
                if with_stats:
                    out += (cache.step_stats(views),)
                return (ntok, nctx, nactive, cache.arrays_of(views)), out

            (tok, ctx, active, arrays), (toks, valid, *stats) = \
                jax.lax.scan(body, (tok, ctx, active, arrays),
                             None, length=K)
            return (toks, valid, *arrays, last.at[slot].set(tok), *stats)

        return decode_fn

    def _decode_spec(self, S, K):
        """Same single-source contract as _prefill_spec, for the
        fused decode modules."""
        import jax.numpy as jnp
        fn = self._decode_build(S, K)
        arrays = self.cache.arrays()
        W = self.scheduler.table_width
        path = self.cache.decode_path(self.model, S, W)
        self._decode_paths.add(path)
        extra = {self.cache.path_key: path}
        where = jnp.asarray(self.cache.idle_where(S, W))
        fp = self._fingerprint('serve-decode', batch=S, span=K,
                               keys='per-request-pos',
                               layout=self.cache.layout_key,
                               tokens='device-slots', **extra)
        example = (self._params, self._buffers, *arrays, self._last,
                   where, jnp.zeros((S,), jnp.int64),
                   jnp.full((S,), self.config.max_slots, jnp.int32),
                   jnp.zeros((S,), bool),
                   jnp.zeros((S,), jnp.int64),
                   jnp.zeros((S,), jnp.int64))
        return fn, fp, example, f'serve.decode[{S}x{K}]', (2, 3, 4)

    def _decode_module(self, S, K):
        sig = ('decode', S, K)
        if sig in self._modules:
            return self._modules[sig]
        return self._get_module(sig, *self._decode_spec(S, K))

    # -- request lifecycle ---------------------------------------------------
    def submit(self, prompt, max_new_tokens=None, rid=None,
               arrival_t=None, deadline_s=None):
        from .. import telemetry
        if isinstance(prompt, Request):
            req = prompt
            if req.deadline_s is None:
                req.deadline_s = self.request_deadline_s(
                    req.max_new_tokens)
        else:
            self._rid += 1
            req = Request(
                rid if rid is not None else f'r{self._rid:05d}',
                prompt, max_new_tokens,
                arrival_t=(arrival_t if arrival_t is not None
                           else self._clock()),
                deadline_s=(deadline_s if deadline_s is not None
                            else self.request_deadline_s(
                                max_new_tokens)))
        if req.seed is None:
            # rid-derived, so the SAME request replayed on any replica
            # sharing the config seed samples the identical stream
            req.seed = request_seed(req.rid, self.config.seed)
        try:
            return self.scheduler.submit(req)
        except RejectedRequest as e:
            telemetry.event('serve_reject', rid=req.rid,
                            reason=e.reason, detail=e.detail)
            raise

    def cancel(self, rid, cause='cancelled'):
        """Evict one in-flight request (client cancel / disconnect):
        frees its blocks, rolls its decoded-token accounting back (the
        preemption path's discipline — a token nobody received must
        not count as delivered throughput), and emits the usual
        finished-request telemetry with the typed cause.  Returns True
        if the rid was live (queued or running), False otherwise.  A
        span in flight is read first (`drain`), so what is rolled back
        is everything the request was ever handed."""
        sched = self.scheduler
        self.drain()
        for req in list(sched.queue):
            if req.rid == rid:
                sched.queue.remove(req)
                sched.finish(req, cause)
                self._note_finished([req], self._clock())
                return True
        for req in list(sched.running):
            if req.rid == rid:
                rolled = len(req.tokens)
                self.decoded_tokens -= rolled
                self._pending_discarded += rolled
                sched.finish(req, cause)
                self._note_finished([req], self._clock())
                return True
        return False

    def _chunk_bucket(self, n):
        return _cc.bucket_pow2(n, cap=self.config.prefill_batch)

    def _prefill_dispatch(self, reqs, ordinal):
        """Dispatch ONE batched prefill over a chunk of same-bucket
        admissions (async), the engine's `ordinal`-th; the pools chain
        through donation so back-to-back chunks pipeline on the
        device.  Returns the un-synced first-token device array
        [chunk bucket]."""
        import jax.numpy as jnp
        P = reqs[0].prompt_bucket
        B = self._chunk_bucket(len(reqs))
        mod = self._prefill_module(P, B)
        ids = np.zeros((B, P), np.int64)
        t0s = np.ones((B,), np.int32)      # padding rows sample row 0
        seeds = np.zeros((B,), np.int64)
        for i, req in enumerate(reqs):
            ids[i, :req.prompt.size] = req.prompt
            t0s[i] = req.prompt.size
            seeds[i] = req.seed or 0
        # padding rows write where nothing is kept (the trash block)
        where = self.cache.prefill_where([r.rid for r in reqs], B, P)
        slots = np.full((B,), self.config.max_slots, np.int32)
        slots[:len(reqs)] = [r.slot for r in reqs]
        tok, first, second, self._last = mod(
            self._params, self._buffers, jnp.asarray(ids),
            jnp.asarray(t0s), *self.cache.arrays(), self._last,
            jnp.asarray(where), jnp.asarray(slots), jnp.asarray(seeds))
        self.cache.set_arrays((first, second))
        now = self._clock()
        for i, req in enumerate(reqs):
            # a recurrent cache: the device row that holds the state
            req.trace_note('prefill', now, bucket=P, chunk=B,
                           dispatch=ordinal,
                           slot=int(where[i]) if self.recurrent else None)
        return tok

    def _prefill_read(self, reqs, toks):
        """A prefill chunk's first tokens, on the host, each handed to
        its request; and the chunk counted, now that the device has
        run it: a counter read beside a device trace (a traced
        benchmark run's) then counts the work the trace holds, not
        what is still queued behind the span in flight."""
        P, B = reqs[0].prompt_bucket, self._chunk_bucket(len(reqs))
        self._prefills += 1
        self.moe_kernel_prefills += ('prefill', P, B) in self._kernel_prefills
        self.prefill_tokens += sum(r.prompt.size for r in reqs)
        self.prefill_padded_tokens += B * P
        for req, tok in zip(reqs, toks):
            # (one that reservation preempted just now has no token)
            if req.state == Request.RUNNING:
                self._prefill_finish(req, tok)
                self._pending_prefilled += 1

    def _prefill_finish(self, req, tok):
        """Record one synced first token (TTFT anchor: the moment the
        host holds it) and finish the request if it is already
        complete."""
        req.tokens.append(int(tok))
        req.first_token_t = self._clock()
        req.trace_note('first_token', req.first_token_t)
        self.decoded_tokens += 1
        if self.config.eos_id is not None \
                and req.tokens[-1] == self.config.eos_id:
            self.scheduler.finish(req, 'eos')
        elif len(req.tokens) >= req.max_new_tokens:
            self.scheduler.finish(req, 'max_tokens')
        return req

    def _decode(self, plan):
        import jax.numpy as jnp
        mod = self._decode_module(plan.batch, plan.span)
        toks, valid, first, second, self._last, *stats = mod(
            self._params, self._buffers, *self.cache.arrays(),
            self._last, jnp.asarray(self.cache.decode_where(plan)),
            jnp.asarray(plan.ctx),
            jnp.asarray(plan.slot), jnp.asarray(plan.active),
            jnp.asarray(plan.limit), jnp.asarray(plan.seed))
        self.cache.set_arrays((first, second))
        return toks, valid, stats

    def _emit_serve_step(self, admitted, t_start, **fields):
        """The ``serve_step`` event, carrying the pending first-token /
        rollback counts.  The pool-shape fields cost a sort of the free
        list and a pass over every owned sequence (``frag_report``), so
        they are built only where a writer or a subscriber reads the
        stream; the flight ring keeps the cheap fields."""
        from .. import telemetry
        if telemetry.streaming():
            frag = self.cache.frag_report()
            fields.update(
                kv_frag_frac=frag['frag_frac'],
                kv_largest_free_run=frag['largest_free_run'])
        telemetry.event('serve_step', intervention=self.interventions,
                        admitted=admitted,
                        queued=len(self.scheduler.queue),
                        free_blocks=self.cache.free_blocks,
                        total_blocks=self.cache.num_blocks,
                        kv_high_water=self.cache.high_water_blocks,
                        prefilled=self._pending_prefilled,
                        discarded=self._pending_discarded,
                        dur_s=round(self._clock() - t_start, 6),
                        **fields)
        self._pending_prefilled = 0
        self._pending_discarded = 0

    def _flush_pending_tokens(self, admitted, t_start):
        """A prefill-only intervention (nothing left running) emits a
        decode-less ``serve_step`` carrying the pending first-token /
        rollback counts, so no delivered token is ever lost to the
        early-return paths."""
        if self._pending_prefilled or self._pending_discarded:
            self._emit_serve_step(admitted, t_start, live=0, batch=0,
                                  span=0, decoded=0, finished=0,
                                  preempted=0, kv_blocks_read=0,
                                  kv_blocks_table=0)

    def _note_finished(self, finished, now):
        from .. import telemetry
        # the full lifecycle trail is copied into an event only where
        # something consumes the stream (the live plane's
        # /requests/<rid> store, a JSONL writer); otherwise the rows
        # stay on the Request and _live_trace serves them
        trails = bool(finished) and telemetry.streaming()
        for req in finished:
            rec = req.record(now)
            telemetry.event('serve_request', **rec)
            if trails:
                # ONE event per finished request (bounded by request
                # count, never by decode steps); joinable with
                # serve_request by rid
                telemetry.event('serve_trace', rid=req.rid,
                                state=req.state, reason=req.reason,
                                prompt_bucket=req.prompt_bucket,
                                trace=[dict(r) for r in req.trace])
            if req.reason == 'deadline':
                telemetry.event(
                    'timeout', op='serve_request', rid=req.rid,
                    budget_s=req.deadline_s, age_s=rec['age_s'])

    # -- the intervention loop -----------------------------------------------
    # the next intervention starts when what is left of the span in
    # flight is this many of the host's own planning times (`wait_s`)
    LEAD = 2.0

    def step(self, now=None):
        """ONE scheduler intervention.  A decode dispatch is kept in
        flight: this call plans and SENDS the span of intervention
        N+1 while N is still running on the device, and only then
        reads, absorbs and books N behind it.  In order (the spans
        ``serve.step`` and its children, the contract PERF.md section
        3 lists beside the metrics that read them): ``deadlines →
        admit → prefill_dispatch → reserve → plan → decode_dispatch``
        (N+1) ``→ decode_sync`` (N) ``→ absorb`` (N) ``→
        first_token_sync`` (of the prefills just sent) ``→
        bookkeeping``.

        So a caller who steps the engine by hand reads a span's tokens
        one call after it was sent: the first call that decodes
        delivers first tokens only, ``interventions`` counts spans
        READ, and ``step_taps`` are those of the span read last.
        ``drain()`` reads what is in flight without sending more;
        ``run()``, ``cancel()`` and ``warmup()`` call it.  A request's
        ``first_token_t`` is still the moment the host holds its first
        token.

        Returns the intervention's progress count (admissions +
        evictions + token steps sent + tokens read); 0 means nothing
        could move at all."""
        from ..telemetry import span
        with span('serve.step'):
            return self._step(now)

    def _step(self, now):
        from .. import telemetry
        span = telemetry.span
        sched = self.scheduler
        now = self._clock() if now is None else now
        t_start = self._clock()
        t_plan = time.monotonic()
        with span('serve.deadlines'):
            breached = sched.check_deadlines(now)
            self._note_finished(breached, now)
        # two-phase admission: chunk same-bucket admissions into
        # batched prefill dispatches (device work pipelines through
        # the donated pool chain); their first tokens stay on the
        # device for the decode span sent below, and the host reads
        # them last
        chunks = []
        with span('serve.admit'):
            while True:
                req = sched.admit_next()
                if req is None:
                    break
                if not chunks or (
                        req.prompt_bucket != chunks[-1][0].prompt_bucket
                        or len(chunks[-1]) >= self.config.prefill_batch):
                    chunks.append([])
                chunks[-1].append(req)
        fresh = [r for c in chunks for r in c]
        admitted = len(fresh)
        firsts = []
        for chunk in chunks:
            with span('serve.prefill_dispatch'):
                firsts.append(self._prefill_dispatch(
                    chunk, self._prefills + len(firsts) + 1))
        # a request of one token is whole once its prefill is sent
        sched.release_sent(fresh)
        with span('serve.reserve'):
            preempted = sched.reserve_span(sched.decode_span)
        # a preempted request's emitted tokens are discarded and will
        # be recomputed — un-count them so tokens_per_s only ever
        # counts DELIVERED tokens once (what is in flight for it was
        # never counted, and is skipped when its span is absorbed)
        discarded = sum(r.discarded_tokens for r in preempted)
        self.decoded_tokens -= discarded
        self._pending_discarded += discarded
        with span('serve.plan'):
            plan = sched.plan()
        read, self._in_flight = self._in_flight, None
        sent = 0
        if plan is not None:
            with span('serve.decode_dispatch'):
                toks_dev, valid_dev, stats_dev = self._decode(plan)
                # what the dispatch reads of what its rows hold, before
                # the rows that end in it give their blocks back
                kv_read, kv_table = self.cache.kv_blocks(plan)
                sched.sent(plan)
                sent = sum(plan.sent)
                self._in_flight = {
                    'plan': plan, 'toks': toks_dev, 'valid': valid_dev,
                    'stats': stats_dev, 'kv': (kv_read, kv_table),
                    'ahead': int(read is not None),
                    'sent_t': time.monotonic()}
            self._plan_s.append(time.monotonic() - t_plan)
            if self._prof is not None:
                # dispatches sent before this one: those read, and the
                # one still in flight
                self._prof.observe(
                    (self.interventions + (read is not None)) * plan.span,
                    sync=toks_dev, span=plan.span)
        n, finished = 0, []
        if read is not None:
            n, finished = self._collect(read)
        for reqs, toks_dev in zip(chunks, firsts):
            with span('serve.first_token_sync'):
                toks = self._await(toks_dev)
            self._prefill_read(reqs, toks)
        with span('serve.bookkeeping'):
            self._note_finished(finished + [r for r in fresh if r.done],
                                self._clock())
            if read is not None:
                self._book(read, n, len(finished), admitted,
                           len(preempted), t_start)
            elif self._in_flight is None:
                # everything finished at prefill (or was evicted):
                # flush the carried first-token counts NOW — no later
                # serve_step will fire to carry them, and the live
                # plane / run_report token accounting must still match
                # decoded_tokens
                self._flush_pending_tokens(admitted, t_start)
        return admitted + len(breached) + sent + n

    def _await(self, arr, sent_t=None, shape=None):
        """The host's copy of a device array, and the engine's own
        clock of the device: when it finished this, and, where the
        host had to wait for it, how long a decode dispatch of `shape`
        ran (from the later of its being sent and the device's
        finishing what was before it)."""
        waited = not arr.is_ready()
        out = np.asarray(arr)
        t = time.monotonic()
        if shape is not None and waited:
            self._span_s.setdefault(
                shape, collections.deque(maxlen=3)).append(
                    t - max(sent_t, self._device_free_t))
        self._device_free_t = t
        return out

    def _collect(self, flight):
        """Read a decode dispatch's tokens and fold them into its
        requests; returns (tokens delivered, requests finished)."""
        from ..telemetry import span
        plan = flight['plan']
        with span('serve.decode_sync'):
            toks = self._await(flight['toks'], flight['sent_t'],
                               (plan.batch, plan.span))
            valid = np.asarray(flight['valid'])
            for stats in flight['stats']:   # counts [span, names]
                self.step_stats += np.asarray(stats['counts'],
                                              np.int64).sum(0)
                self.step_taps = stats['taps']
        with span('serve.absorb'):
            finished, n = self.scheduler.absorb(plan, toks, valid)
        # every valid token is one live row's state rewritten
        flight['rows'] = int(valid.sum())
        return n, finished

    def _book(self, flight, n, finished, admitted, preempted, t_start):
        """Count a dispatch that was read, and emit its serve_step."""
        from .. import telemetry
        plan = flight['plan']
        kv_read, kv_table = flight['kv']
        self.decoded_tokens += n
        self.interventions += 1
        self.decode_dispatches_ahead += flight['ahead']
        self.kv_blocks_read += kv_read * plan.span
        self.kv_blocks_table += kv_table * plan.span
        if self.recurrent:
            self.state_rows_updated += flight['rows']
        self._emit_serve_step(
            admitted, t_start, live=len(plan.requests),
            batch=plan.batch, span=plan.span, decoded=n,
            finished=finished, preempted=preempted,
            kv_blocks_read=kv_read, kv_blocks_table=kv_table,
            ahead=flight['ahead'])
        telemetry.add('serve.decoded_tokens', n)

    def drain(self):
        """Read, absorb and book the decode dispatch in flight, if
        there is one, without sending another: after it the host holds
        every token the device was asked for.  Returns the tokens it
        delivered."""
        flight, self._in_flight = self._in_flight, None
        if flight is None:
            return 0
        from ..telemetry import span
        t_start = self._clock()
        with span('serve.step'):
            n, finished = self._collect(flight)
            with span('serve.bookkeeping'):
                self._note_finished(finished, self._clock())
                self._book(flight, n, len(finished), 0, 0, t_start)
        return n

    def wait_s(self):
        """Seconds the caller may still hand arrivals over before the
        next intervention has to start (0: start it now).  An
        intervention fixes the next span while the one in flight still
        runs, and a request that arrives after that waits a whole span
        longer; so where arrivals may still come (`run()` with
        requests not yet due, `ServingFrontend`'s loop) the next one is
        taken only when what is left of the span in flight, reckoned
        from the measured length of the last dispatches of its shape,
        is `LEAD` times the host's own measured planning time.  With
        nothing in flight, or nothing measured yet, that is now."""
        flight = self._in_flight
        if flight is None:
            return 0.0
        plan = flight['plan']
        lengths = self._span_s.get((plan.batch, plan.span))
        if not lengths or not self._plan_s:
            return 0.0
        ends = max(flight['sent_t'], self._device_free_t) \
            + statistics.median_high(lengths)
        lead = self.LEAD * statistics.median_high(self._plan_s)
        return max(0.0, ends - lead - time.monotonic())

    def run(self, requests=(), timeout_s=None):
        """Drive to drain: submit `requests` honoring their
        ``arrival_t`` offsets (the Poisson load path), loop
        interventions until every request completes or evicts, and
        read what is still in flight.  While requests are still to
        come it keeps handing them over and takes the next
        intervention when `wait_s` says so; a backlog plans at once.
        Returns the report dict."""
        from ..telemetry import span
        pending = sorted(requests, key=lambda r: r.arrival_t)
        sched = self.scheduler
        t0 = self.now_fn()
        start = self._clock()
        fin0 = len(sched.finished)
        tok0 = self.decoded_tokens
        kv0 = (self.kv_blocks_read, self.kv_blocks_table)
        state0 = (self.state_rows_updated,
                  sched.counters.get('decode_steps', 0))
        # arrival offsets land on the engine clock at release time
        for r in pending:
            r.arrival_t = start + max(0.0, r.arrival_t)
        try:
            while pending or sched.queue or sched.running:
                now = self._clock()
                if timeout_s is not None and now - start > timeout_s:
                    # what the device was asked for is delivered first
                    self.drain()
                    timed_out = []
                    for req in list(sched.running) + list(sched.queue):
                        if req in sched.queue:
                            sched.queue.remove(req)
                        sched.finish(req, 'engine_timeout')
                        timed_out.append(req)
                    # same telemetry as any other eviction: these
                    # requests must not vanish from the live plane /
                    # run_report during exactly the overload that
                    # timed the run out
                    self._note_finished(timed_out, self._clock())
                    pending = []
                    break
                while pending and pending[0].arrival_t <= now:
                    self.submit(pending.pop(0))
                if not sched.queue and not sched.running:
                    if pending:
                        with span('serve.wait_arrival'):
                            time.sleep(min(0.05, max(
                                0.0, pending[0].arrival_t - now)))
                    continue
                if pending:
                    wait = min(self.wait_s(), 0.05,
                               pending[0].arrival_t - now)
                    if wait > 0:
                        with span('serve.wait_span'):
                            time.sleep(wait)
                        continue
                if self.step(now=now) == 0 and not sched.running \
                        and sched.queue:
                    # nothing live and the head of the queue cannot be
                    # admitted even into an empty pool: it can never
                    # run — evict instead of spinning forever
                    req = sched.queue.popleft()
                    sched.finish(req, 'oom')
                    self._note_finished([req], self._clock())
            # a span whose every row was evicted meanwhile
            self.drain()
        finally:
            if self._prof is not None:
                self._prof.close()
        return self.report(wall_s=self.now_fn() - t0,
                           finished_from=fin0, tokens_from=tok0,
                           kv_from=kv0, state_from=state0)

    # -- reporting / stats ---------------------------------------------------
    def report(self, wall_s=None, finished_from=0, tokens_from=0,
               kv_from=(0, 0), state_from=(0, 0)):
        """Aggregate latency/throughput report — over the whole engine
        life by default, or over one run()'s window (its requests, its
        decoded tokens and the KV blocks its dispatches read) when the
        slicing args are given."""
        now = self._clock()
        sched = self.scheduler
        recs = [r.record(now) for r in sched.finished[finished_from:]]
        ttfts = sorted(r['ttft_s'] for r in recs
                       if r['ttft_s'] is not None)
        tpots = [r['tpot_s'] for r in recs if r['tpot_s'] is not None]
        lates = sorted(r['submit_late_s'] for r in recs
                       if r['submit_late_s'] is not None)
        waits = sorted(r['queue_wait_s'] for r in recs
                       if r['queue_wait_s'] is not None)

        def pct(sorted_vals, q):
            if not sorted_vals:
                return None
            i = min(len(sorted_vals) - 1,
                    int(math.ceil(q * len(sorted_vals))) - 1)
            return sorted_vals[max(0, i)]

        decoded = self.decoded_tokens - tokens_from
        return {
            'requests': recs,
            'counters': dict(sched.counters),
            'decoded_tokens': decoded,
            'interventions': self.interventions,
            'wall_s': wall_s,
            'tokens_per_s': decoded / wall_s if wall_s else None,
            'ttft_p50_s': pct(ttfts, 0.50),
            'ttft_p99_s': pct(ttfts, 0.99),
            'tpot_mean_s': (sum(tpots) / len(tpots)) if tpots else None,
            # how late run() handed requests over (the generator's
            # lateness) and how long they then waited for a slot
            'submit_late_p50_s': pct(lates, 0.50),
            'submit_late_p95_s': pct(lates, 0.95),
            'queue_wait_p50_s': pct(waits, 0.50),
            'queue_wait_p95_s': pct(waits, 0.95),
            'compile_count': self.compile_count,
            'modules': sorted(str(s) for s in self._modules),
            'audit': sched.audit(),
            **self._kv_read_stats(*kv_from),
            **self._state_stats(*state_from),
            **self.counts(),
        }

    def counts(self):
        """Counters over the engine's life that a caller differences
        itself: prompt positions prefilled (true, and the buckets'),
        the prefill dispatches that took a routed model's grouped
        kernel, the decode dispatches that were sent while the one
        before them had not been read (of `interventions`, counted as
        each is read), what the model's decode steps handed back
        (`model.step_stat_names`), and a cache's own (`counters`: a
        `LayerGroupKVCache`'s blocks held and read by group)."""
        return {'prefill_tokens': self.prefill_tokens,
                'prefill_padded_tokens': self.prefill_padded_tokens,
                'moe_kernel_prefills': self.moe_kernel_prefills,
                'decode_dispatches_ahead': self.decode_dispatches_ahead,
                **{k: int(v) for k, v in zip(self.step_stat_names,
                                             self.step_stats)},
                **getattr(self.cache, 'counters', {})}

    def _state_stats(self, rows_from=0, steps_from=0):
        """A recurrent cache's counters (nothing for the paged pool):
        live rows x token steps of the decode dispatches, the bytes the
        state holds, and whether every decode module built so far took
        the Pallas update."""
        if not self.recurrent:
            return {}
        return {'state_rows_updated': self.state_rows_updated - rows_from,
                'state_bytes': self.cache.state_bytes,
                'state_bytes_per_row': self.cache.bytes_per_slot,
                'token_steps': self.scheduler.counters.get(
                    'decode_steps', 0) - steps_from,
                'state_kernel': self._decode_paths == {'kernel'}}

    def _kv_read_stats(self, read_from=0, table_from=0):
        """What the decode dispatches read of what their tables hold
        (blocks a token step, summed), and whether every decode module
        built so far took the Pallas kernel."""
        read = self.kv_blocks_read - read_from
        table = self.kv_blocks_table - table_from
        return {'kv_blocks_read': read, 'kv_blocks_table': table,
                'kv_read_share': read / table if table else None,
                'paged_kernel': not self.recurrent
                and self._decode_paths == {'kernel'}}

    def stats(self):
        return {'compile_count': self.compile_count,
                'modules': sorted(str(s) for s in self._modules),
                'interventions': self.interventions,
                'decoded_tokens': self.decoded_tokens,
                'free_blocks': self.cache.free_blocks,
                'kv_frag': self.cache.frag_report(),
                **self._kv_read_stats(), **self._state_stats()}

    # -- AOT / declared bucket set -------------------------------------------
    def bucket_set(self):
        """The declared compiled-module signatures — what
        ``tools/precompile.py --serve`` AOT-compiles and what the lint
        gate sweeps."""
        c = self.config
        return {'prompt_buckets': list(c.prompt_buckets),
                'batch_buckets': list(c.batch_buckets),
                'prefill_chunks': list(_pow2_chain(1, c.prefill_batch)),
                'decode_span': c.decode_span,
                'block_size': c.block_size,
                'max_slots': c.max_slots,
                'max_model_len': c.max_model_len}

    def warmup(self):
        """Build AND execute every declared module once, on inert
        inputs (all rows point at the trash block, decode lanes
        inactive), so the call-path XLA compile happens NOW — the
        deterministic cold-start a serving deploy pays once, after
        which run() never compiles or first-call-stalls regardless of
        which buckets the live traffic hits.  Returns stats()."""
        import jax.numpy as jnp
        params, buffers = self._params, self._buffers
        cache = self.cache
        self.drain()
        # every row writes the spare entry of the last-token vector
        spare = self.config.max_slots
        for P in self.config.prompt_buckets:
            for B in _pow2_chain(1, self.config.prefill_batch):
                mod = self._prefill_module(P, B)
                tok, first, second, self._last = mod(
                    params, buffers, jnp.zeros((B, P), jnp.int64),
                    jnp.full((B,), P, jnp.int32), *cache.arrays(),
                    self._last,
                    jnp.asarray(cache.prefill_where((), B, P)),
                    jnp.full((B,), spare, jnp.int32),
                    jnp.zeros((B,), jnp.int64))
                cache.set_arrays((first, second))
                np.asarray(tok)
        W = self.scheduler.table_width
        for S in self.config.batch_buckets:
            mod = self._decode_module(S, self.config.decode_span)
            toks, _valid, first, second, self._last, *_stats = mod(
                params, buffers, *cache.arrays(), self._last,
                jnp.asarray(cache.idle_where(S, W)),
                jnp.zeros((S,), jnp.int64),
                jnp.full((S,), spare, jnp.int32),
                jnp.zeros((S,), bool), jnp.zeros((S,), jnp.int64),
                jnp.zeros((S,), jnp.int64))
            cache.set_arrays((first, second))
            np.asarray(toks)
        if self.live is not None:
            # every declared module just built+ran: compiles from here
            # on are anomalies the drift monitor flags
            self.live.mark_steady()
        return self.stats()

    def precompile(self):
        """Export + AOT-compile every declared serving module into the
        persistent compile cache (PR 7); returns sidecar entries for
        ``compile_cache.write_precompile_manifest``.  A later engine in
        a fresh process deserializes instead of tracing."""
        import jax
        entries, errors = [], {}
        if not _cc.enabled():
            return entries, {'cache': 'compile cache disabled'}
        specs = [(f'serve-prefill bucket {P} chunk {B}',
                  lambda P=P, B=B: self._prefill_spec(P, B))
                 for P in self.config.prompt_buckets
                 for B in _pow2_chain(1, self.config.prefill_batch)]
        specs += [(f'serve-decode batch {S} span '
                   f'{self.config.decode_span}',
                   lambda S=S: self._decode_spec(
                       S, self.config.decode_span))
                  for S in self.config.batch_buckets]
        for desc, make in specs:
            try:
                # the EXACT spec the runtime modules compile from —
                # one source, so the AOT artifact can never drift
                fn, fp, example, name, _donate = make()
                if fp is None:
                    errors[desc] = 'no fingerprint'
                elif _cc.get('exec', fp) is None and \
                        not _cc.store_executable(
                            fp, jax.jit(fn), example, name=name,
                            aot_compile=True):
                    errors[desc] = 'export failed'
                else:
                    entries.append({'tier': 'exec', 'fingerprint': fp,
                                    'description': desc})
            except Exception as e:
                errors[desc] = repr(e)
        return entries, errors


class DecodeAuditLayer(nn.Layer):
    """One paged decode step as an ``analysis.targets`` audit surface:
    a Layer whose forward runs the serving engine's per-step math
    (paged views + ragged attention over the pool) so ``tpu_lint
    --hlo``/``--plan`` can lower and audit the serving path with the
    same machinery as the train steps.  `k_pools`/`v_pools` are the
    layers' pools stacked: ``[num_layers, num_blocks, block_size,
    num_heads, head_dim]``."""

    def __init__(self, model):
        super().__init__()
        self.model = model

    def forward(self, tok, k_pools, v_pools, tables, ctx):
        import jax.numpy as jnp

        def raw(t):
            return t.value if hasattr(t, 'value') else t

        kp, vp = raw(k_pools), raw(v_pools)
        tbl, cx = raw(tables), raw(ctx)
        L = self.model.config.num_layers
        views = [PagedCacheView(kp[l], vp[l], tbl, cx, cx + 1)
                 for l in range(L)]
        logits, views = self.model(tok, caches=views, pos=cx)
        nk = jnp.stack([raw(v.k_pool) for v in views])
        nv = jnp.stack([raw(v.v_pool) for v in views])
        return logits, nk, nv
