"""Continuous-batching request scheduler.

The unit of scheduling is the *decode intervention*: between two
interventions the engine runs one compiled multi-step decode over the
live set; at each intervention the scheduler

- releases newly-arrived requests from the load/clock into the queue,
- **admits** queued requests while a batch slot AND enough free KV
  blocks exist (prefill happens immediately on admission),
- **evicts** finished requests (EOS, max tokens, deadline breach) and
  frees their blocks — the freed capacity backfills from the queue at
  the SAME intervention, so the batch never idles half-empty while
  work queues,
- **reserves** blocks so every live sequence can absorb the next
  fused decode span without any allocation inside the compiled step.

When reservation cannot cover the live set (pool pressure), the
youngest running request is *preempted* back to the queue — its
blocks free immediately and it re-prefills later (recompute-style
preemption, the simple/robust vLLM policy).

**Planning is by counts.**  The engine sends the decode span of
intervention N+1 while N is still running on the device, so the
scheduler never reads a token to schedule: a request carries how many
token steps have been sent for it and not yet absorbed
(``Request.dispatched``), and ``reserve_span`` and ``plan`` reckon its
context as ``ctx + dispatched``, capped at its ``limit``.  The input
token of a row stays on the device, in the engine's vector of last
tokens, at the request's ``slot`` (held from admission to the end).  A
row that ends by ``max_new_tokens`` is known to end in N as soon as N
is sent: ``sent(plan)`` gives its slot and blocks back at once
(``Request.released``; it stays in ``running`` until its tokens are
absorbed), so the next admission has them when N+1 is planned.  The
device runs what it is sent in order, so a block freed on the host
while N is in flight is never rewritten before N has read it.  A row
that ends on ``eos_id`` cannot be foreseen: the host learns it when it
absorbs N and frees the slot one span late.

All host-side bookkeeping: the scheduler never touches a device
array.  The engine asks for a :class:`DecodePlan` (padded numpy
tables/lengths bucketed to the declared pow2 batch set) and reports
back the decoded tokens.
"""
import collections
import time

import numpy as np

from .kv_cache import TRASH_BLOCK, blocks_for

__all__ = ['Request', 'DecodePlan', 'ContinuousBatchingScheduler',
           'RejectReason', 'RejectedRequest']


class RejectReason:
    """The typed load-shedding taxonomy — ONE source of truth shared
    by ``ServingEngine.submit`` (EXCEEDS_POOL) and the serving front
    door (QUEUE_FULL/DRAINING), so the engine, the HTTP plane, the
    router and run_report can never disagree on what a rejection is.
    Each reason maps to the HTTP status the frontend returns."""

    EXCEEDS_POOL = 'exceeds_pool'   # can NEVER run on this engine
    QUEUE_FULL = 'queue_full'       # admission queue at capacity now
    DRAINING = 'draining'           # engine draining; retry elsewhere

    ALL = (EXCEEDS_POOL, QUEUE_FULL, DRAINING)
    HTTP_STATUS = {EXCEEDS_POOL: 413, QUEUE_FULL: 429, DRAINING: 503}


class RejectedRequest(ValueError):
    """A typed admission refusal.  Subclasses ValueError so callers
    that predate the taxonomy (tests, scripts catching ValueError
    from ``submit``) keep working unchanged."""

    def __init__(self, reason, detail, rid=None):
        assert reason in RejectReason.ALL, reason
        super().__init__(detail)
        self.reason = reason
        self.detail = detail
        self.rid = rid

    @property
    def http_status(self):
        return RejectReason.HTTP_STATUS[self.reason]


class Request:
    """One generation request moving through the serving engine."""

    QUEUED, RUNNING, DONE, EVICTED = 'queued', 'running', 'done', \
        'evicted'

    def __init__(self, rid, prompt, max_new_tokens, *, arrival_t=0.0,
                 deadline_s=None, seed=None):
        self.rid = rid
        self.prompt = np.asarray(prompt, np.int64).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError('empty prompt')
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError('max_new_tokens must be >= 1')
        self.arrival_t = float(arrival_t)
        self.deadline_s = deadline_s
        # per-request sampling base seed (ops/sampling discipline):
        # every token this request samples derives its key from
        # (seed, absolute position), NOT from batch composition or
        # scheduling history — None means the engine derives one from
        # the rid at submit, so a replayed retry on another replica
        # continues the identical stream
        self.seed = None if seed is None else int(seed)
        self.state = Request.QUEUED
        self.reason = None          # eos | max_tokens | deadline | ...
        self.tokens = []            # decoded token ids (ints)
        self.ctx = 0                # cache positions written AND absorbed
        # token steps sent to the device and not yet absorbed, and the
        # row of the engine's last-token vector held while there are
        # steps still to send (see the module's docstring)
        self.dispatched = 0
        self.slot = None
        self.prompt_bucket = None   # padded prefill length (pow2)
        self.first_token_t = None   # wall time of the first token
        self.finish_t = None
        self.preemptions = 0
        self.discarded_tokens = 0   # last preemption's recompute debt
        self.trace = []             # lifecycle rows (see trace_note)

    def trace_note(self, stage, t, **tags):
        """Append one lifecycle row: the queued→admitted→prefill→
        first_token→decode_span*→finished/evicted/preempted trail,
        each row timestamped on the engine clock and tagged with its
        cause/bucket.  Host-side list append — the engine emits the
        whole trail as ONE ``serve_trace`` event at finish, and
        ``telemetry.live`` serves it at ``/requests/<rid>``."""
        row = {'stage': stage, 't': round(float(t), 6)}
        row.update({k: v for k, v in tags.items() if v is not None})
        self.trace.append(row)
        return row

    @property
    def done(self):
        return self.state in (Request.DONE, Request.EVICTED)

    @property
    def released(self):
        """Running with slot and blocks given back already: everything
        it will ever emit has been sent and is in flight."""
        return self.state == Request.RUNNING and self.slot is None

    # emitted-token accounting: after prefill ctx == t0 and ONE token
    # exists; each decode step advances ctx and emits one more.  The
    # last useful decode step is the one reaching ctx == limit - 1.
    @property
    def limit(self):
        return self.prompt.size + self.max_new_tokens - 1

    def record(self, now, ttft_anchor=None):
        """Latency summary for reports/telemetry."""
        anchor = self.arrival_t if ttft_anchor is None else ttft_anchor
        ttft = None if self.first_token_t is None \
            else self.first_token_t - anchor
        tpot = None
        if self.finish_t is not None and self.first_token_t is not None \
                and len(self.tokens) > 1:
            tpot = (self.finish_t - self.first_token_t) \
                / (len(self.tokens) - 1)
        # request-level times are no with-blocks, so they are fields:
        # the first `queued` / `admitted` rows of the trace, less the
        # time the request was due
        first = {}
        for row in self.trace:
            first.setdefault(row['stage'], row['t'])
        return {'rid': self.rid, 'state': self.state,
                'reason': self.reason, 'prompt_len': int(self.prompt.size),
                'tokens': len(self.tokens), 'ttft_s': ttft,
                'tpot_s': tpot, 'preemptions': self.preemptions,
                'age_s': (now - self.arrival_t),
                'submit_late_s': None if 'queued' not in first
                else first['queued'] - self.arrival_t,
                'queue_wait_s': None if 'admitted' not in first
                else first['admitted'] - self.arrival_t}


class DecodePlan:
    """One intervention's padded decode inputs (host numpy)."""

    def __init__(self, requests, batch_bucket, table_width, span,
                 groups=None):
        self.requests = list(requests)        # live order, <= bucket
        self.batch = int(batch_bucket)
        self.span = int(span)
        # one table a row, or one a group of layers (a cache that says
        # `table_groups`): [batch, groups, width]
        shape = (self.batch, table_width) if groups is None \
            else (self.batch, int(groups), table_width)
        self.tables = np.full(shape, TRASH_BLOCK, np.int32)
        self.ctx = np.zeros((self.batch,), np.int64)
        # the rows of the device's last-token vector the steps read
        # their input from and write their last token to; a padding
        # row names the spare entry past the last slot
        self.slot = np.zeros((self.batch,), np.int32)
        self.active = np.zeros((self.batch,), bool)
        self.limit = np.zeros((self.batch,), np.int64)
        self.seed = np.zeros((self.batch,), np.int64)
        # token steps this plan sends for each request, and the
        # request's count of preemptions when it was planned (a span
        # in flight across a preemption is discarded)
        self.sent = [0] * len(self.requests)
        self.epoch = [r.preemptions for r in self.requests]

    def kv_blocks(self, block_size):
        """(read, table): the KV blocks one token step of this plan
        has to read — each active row's context at the span's end,
        rounded up to blocks, and one block for every other row (its
        length is its context plus the token just written) — against
        the blocks its table holds, which is what a gather of the
        whole table reads.  Host arithmetic; no device work."""
        width = self.tables.shape[1]
        ends = np.where(self.active, self.ctx + self.span, self.ctx + 1)
        read = np.minimum(-(-ends // block_size), width)
        return int(read.sum()), self.batch * width


class ContinuousBatchingScheduler:
    """Admission/eviction policy over a :class:`PagedKVCache`.

    ``bucket_fn(prompt_len) -> padded prefill length`` comes from the
    engine (its declared pow2 prompt-bucket set); ``batch_buckets`` is
    the declared pow2 set of decode batch sizes (must contain
    ``max_slots``).
    """

    def __init__(self, cache, *, max_slots, batch_buckets, bucket_fn,
                 max_model_len, decode_span=1, eos_id=None,
                 now_fn=time.monotonic):
        self.cache = cache
        self.max_slots = int(max_slots)
        self.batch_buckets = tuple(sorted(set(
            int(b) for b in batch_buckets)))
        if self.max_slots not in self.batch_buckets:
            raise ValueError(
                f'batch_buckets {self.batch_buckets} must contain '
                f'max_slots {self.max_slots}')
        self.bucket_fn = bucket_fn
        self.max_model_len = int(max_model_len)
        self.decode_span = max(1, int(decode_span))
        self.eos_id = eos_id
        self.now_fn = now_fn
        self.table_width = blocks_for(self.max_model_len,
                                      cache.block_size)
        self.queue = collections.deque()
        self.running = []            # admission order (oldest first)
        self.finished = []
        self.counters = collections.Counter()
        # rows of the engine's last-token vector nobody holds (LIFO);
        # entry `max_slots` is the spare one of a plan's padding rows
        self._free_slots = list(range(self.max_slots - 1, -1, -1))

    # -- submission ---------------------------------------------------------
    def submit(self, req):
        total = req.prompt.size + req.max_new_tokens
        if total > self.max_model_len:
            self.counters['rejected'] += 1
            raise RejectedRequest(
                RejectReason.EXCEEDS_POOL,
                f'request {req.rid}: prompt+new {total} exceeds '
                f'max_model_len {self.max_model_len}', rid=req.rid)
        # feasibility: the request's WORST-CASE block need (prefill
        # bucket or its full trajectory, whichever is larger) must fit
        # an empty pool — otherwise reservation would preempt it
        # against itself forever (admit -> decode -> self-preempt ->
        # re-admit livelock)
        bucket = int(self.bucket_fn(req.prompt.size))
        if not self.cache.holds(bucket, req.limit):
            worst = blocks_for(max(bucket, req.limit),
                               self.cache.block_size)
            self.counters['rejected'] += 1
            raise RejectedRequest(
                RejectReason.EXCEEDS_POOL,
                f'request {req.rid}: needs {worst} KV blocks at its '
                f'longest, pool only has {self.cache.num_blocks - 1}',
                rid=req.rid)
        self.queue.append(req)
        self.counters['submitted'] += 1
        req.trace_note('queued', self.now_fn(),
                       prompt_len=int(req.prompt.size),
                       max_new_tokens=req.max_new_tokens,
                       deadline_s=req.deadline_s)
        return req

    # -- admission ----------------------------------------------------------
    def admit_next(self):
        """Admit the head of the queue if a slot and blocks exist;
        returns the Request (caller prefills it) or None."""
        if not self.queue or not self._free_slots:
            return None
        req = self.queue[0]
        bucket = int(self.bucket_fn(req.prompt.size))
        # the paged pool's prefill scatter writes the whole
        # (block-rounded) bucket, so `prefill_positions` is the larger
        # of the two there; reserving one decode span up front keeps
        # admission from thrashing against the very next reservation
        # pass.  `written` tells a cache with window layers how much of
        # the prompt no later query sees: those blocks are never taken
        need = self.cache.prefill_positions(
            bucket, min(req.prompt.size + self.decode_span, req.limit))
        if not self.cache.ensure(req.rid, need, written=req.prompt.size):
            return None
        self.queue.popleft()
        req.state = Request.RUNNING
        req.prompt_bucket = bucket
        req.ctx = req.prompt.size
        req.dispatched = 0
        req.slot = self._free_slots.pop()
        self.running.append(req)
        self.counters['admitted'] += 1
        req.trace_note('admitted', self.now_fn(), bucket=bucket,
                       blocks=len(self.cache.owned(req.rid)))
        return req

    # -- eviction / completion ----------------------------------------------
    def _give_back(self, req):
        """`req`'s blocks and slot go back to their free lists (once:
        a released request holds neither)."""
        self.cache.free_seq(req.rid)
        if req.slot is not None:
            self._free_slots.append(req.slot)
            req.slot = None

    def finish(self, req, reason):
        if not req.released:
            self._give_back(req)
        req.dispatched = 0
        req.state = Request.DONE if reason in ('eos', 'max_tokens') \
            else Request.EVICTED
        req.reason = reason
        req.finish_t = self.now_fn()
        if req in self.running:
            self.running.remove(req)
        self.finished.append(req)
        self.counters['evicted' if req.state == Request.EVICTED
                      else 'completed'] += 1
        req.trace_note('finished' if req.state == Request.DONE
                       else 'evicted', req.finish_t, cause=reason,
                       tokens=len(req.tokens))

    def preempt_youngest(self):
        """Pool pressure: push the newest running request that still
        holds blocks back to the queue head (recompute-style — its
        blocks free now, it re-prefills from scratch later; what is in
        flight for it is discarded when its span is absorbed)."""
        held = [r for r in self.running if not r.released]
        if not held:
            return None
        req = held[-1]
        self.running.remove(req)
        self._give_back(req)
        req.dispatched = 0
        req.state = Request.QUEUED
        # the discarded work is recomputed after re-admission — the
        # engine rolls its decoded-token accounting back by this much
        # so throughput never counts a token twice
        req.discarded_tokens = len(req.tokens)
        self.counters['discarded_tokens'] += req.discarded_tokens
        req.tokens = []
        req.ctx = 0
        req.first_token_t = None
        req.preemptions += 1
        self.queue.appendleft(req)
        self.counters['preempted'] += 1
        req.trace_note('preempted', self.now_fn(), cause='pool',
                       discarded_tokens=req.discarded_tokens)
        return req

    def check_deadlines(self, now):
        """Evict running AND queued requests past their deadline —
        the watchdog-budget starvation guard."""
        breached = [r for r in list(self.running) + list(self.queue)
                    if r.deadline_s is not None
                    and now - r.arrival_t > r.deadline_s]
        for req in breached:
            if req in self.queue:
                self.queue.remove(req)
            self.finish(req, 'deadline')
        return breached

    # -- decode planning -----------------------------------------------------
    def reserve_span(self, span):
        """Reserve blocks so every live sequence can write `span` more
        positions past what has been sent for it (capped at its own
        limit).  Preempts the youngest request(s) on pool pressure;
        returns the preempted list."""
        preempted = []
        i = 0
        while i < len(self.running):
            req = self.running[i]
            if req.released:
                i += 1
                continue
            at = req.ctx + req.dispatched
            need = min(at + span, req.limit)
            # `written`: a window group releases what no query from
            # position `at` on can see before it grows (a span in
            # flight read its table when it was planned, and runs
            # before anything that is given a released block)
            if self.cache.ensure(req.rid, need, written=at):
                i += 1
                continue
            victim = self.preempt_youngest()
            preempted.append(victim)
            if victim is req:
                continue            # re-check from the same index
            # a younger victim freed blocks; retry this request
        return preempted

    def plan(self, span=None):
        """Build the DecodePlan for the rows that have token steps
        still to be sent (None when there is none), by counts alone: a
        row stands at ``ctx + dispatched``.  Batch is padded to the
        smallest declared pow2 bucket >= live count; padding rows
        point at the trash block and stay inactive.  Changes nothing:
        ``sent(plan)`` books a plan that was dispatched."""
        rows = [r for r in self.running if not r.released]
        if not rows:
            return None
        span = self.decode_span if span is None else int(span)
        batch = next(b for b in self.batch_buckets if b >= len(rows))
        plan = DecodePlan(rows, batch, self.table_width, span,
                          groups=getattr(self.cache, 'table_groups',
                                         None))
        plan.slot[:] = self.max_slots
        for i, req in enumerate(rows):
            at = req.ctx + req.dispatched
            plan.tables[i] = self.cache.table_row(req.rid,
                                                  self.table_width)
            plan.ctx[i] = at
            plan.slot[i] = req.slot
            plan.active[i] = at < req.limit
            plan.limit[i] = req.limit
            plan.seed[i] = req.seed or 0
            plan.sent[i] = max(0, min(span, req.limit - at))
        return plan

    def release_sent(self, reqs):
        """Of `reqs`, those for which every token step they will ever
        run has been sent give their slot and blocks back now.  They
        stay in ``running`` until what is in flight for them has been
        absorbed."""
        for req in reqs:
            if req.state == Request.RUNNING and not req.released \
                    and req.ctx + req.dispatched >= req.limit:
                self._give_back(req)

    def sent(self, plan):
        """`plan` was dispatched: count its token steps as in flight
        and release the rows that end in it by ``max_new_tokens``."""
        for req, n in zip(plan.requests, plan.sent):
            req.dispatched += n
        self.release_sent(plan.requests)

    def absorb(self, plan, toks, valid):
        """Fold one decode span's outputs back into the requests:
        append valid tokens, finish on EOS / max tokens.  ``toks`` and
        ``valid`` are ``[span, batch]`` host arrays.  A request that
        was preempted, cancelled or timed out while the span was in
        flight is skipped: its tokens are discarded.  Returns (the
        requests that finished, the tokens delivered)."""
        finished = []
        delivered = 0
        now = self.now_fn()
        for i, req in enumerate(plan.requests):
            if req.state != Request.RUNNING \
                    or req.preemptions != plan.epoch[i]:
                continue
            req.dispatched -= plan.sent[i]
            emitted = 0
            finish_reason = None
            for k in range(plan.span):
                if not valid[k, i] or req.done:
                    break
                tok = int(toks[k, i])
                req.tokens.append(tok)
                emitted += 1
                if self.eos_id is not None and tok == self.eos_id:
                    finish_reason = 'eos'
                    break
                if len(req.tokens) >= req.max_new_tokens:
                    finish_reason = 'max_tokens'
                    break
            req.ctx = min(req.ctx + emitted, req.limit)
            delivered += emitted
            if emitted:
                # ONE trace row per intervention per live request,
                # noted BEFORE any finish row so the trail stays in
                # lifecycle order
                req.trace_note('decode_span', now, span=plan.span,
                               emitted=emitted,
                               tokens=len(req.tokens))
            if finish_reason is not None:
                self.finish(req, finish_reason)
            if req.done:
                finished.append(req)
        self.counters['decode_steps'] += plan.span
        return finished, delivered

    # -- invariants ----------------------------------------------------------
    def audit(self):
        """Scheduler+allocator invariants; list of violations."""
        problems = list(self.cache.audit())
        states = collections.Counter(r.state for r in self.running)
        if set(states) - {Request.RUNNING}:
            problems.append(f'non-running request in live set: {states}')
        held = [req for req in self.running if not req.released]
        for req in held:
            covered = len(self.cache.owned(req.rid)) \
                * self.cache.block_size
            if covered < req.ctx + req.dispatched:
                problems.append(
                    f'request {req.rid}: ctx {req.ctx} and '
                    f'{req.dispatched} steps in flight exceed its '
                    f'{covered} covered cache positions')
        for req in self.finished:
            if self.cache.owned(req.rid):
                problems.append(
                    f'finished request {req.rid} still owns blocks')
        slots = [req.slot for req in held]
        if sorted(slots + self._free_slots) != list(range(self.max_slots)):
            problems.append(
                f'token slots held {sorted(slots)} and free '
                f'{sorted(self._free_slots)} are not 0..'
                f'{self.max_slots - 1} once each')
        live = {req.rid for req in held}
        for sid in self.cache.owners():
            if sid not in live:
                problems.append(
                    f'sequence {sid} owns cache but is not running')
        return problems
