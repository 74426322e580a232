"""Serving front door (streaming HTTP plane + multi-engine router).

Contracts pinned here:

- **per-request positional sampling keys** (ops/sampling): token at
  absolute position ``pos`` of a request is keyed by
  ``fold_in(fold_in(key(seed), pos), row)`` — so the engine's
  sampled streams are bit-exact vs sequential batch-1 ``generate``
  (greedy AND temperature>0, one parametrized test), and a stream
  replayed as prompt+emitted-prefix resumes bit-exactly (the router's
  retry primitive);
- **typed admission**: ``submit()`` refuses with RejectedRequest
  (``RejectReason`` taxonomy, HTTP status per reason, ``serve_reject``
  event) instead of a bare ValueError; ``cancel()`` rolls token
  accounting back (PR-12 preemption bookkeeping);
- **the HTTP door** (serving/frontend.py): ``POST /v1/generate``
  streams SSE over chunked transfer, sheds load with typed
  rejections + Retry-After, evicts on client disconnect, drains on
  command;
- **the router** (serving/router.py): KV-occupancy-aware dispatch, a
  replica dying mid-stream is retried on a survivor bit-exactly with
  at-most-once token delivery, forced ``slo_breach`` latches drain
  the replica and promote the warm spare, and EVERY rid lands in
  exactly one terminal state (``check_invariants``, the chaos-I1-I7
  posture);
- **serving chaos kinds** (resilience/chaos.py): replica_kill /
  replica_hang / client_disconnect / slow_client ride FaultPlan with
  the ``after_tokens`` stream clock, stay out of the seeded
  GENERATABLE draw stream, and fire deterministically through
  ServingFaultInjector;
- **tp>1 sharded pool**: the engine on a dp1xtp2 virtual CPU mesh is
  bit-exact vs tp=1 with a clean audit and a pool actually sharded
  over 'tp';
- **run_report**: serve_reject / fleet_event land in the serving
  section (shed taxonomy + fleet control-plane timeline).

File name sorts before test_host_embedding so tier-1 runs it.
"""
import http.client
import json
import os
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import telemetry
from paddle_tpu.models.gpt import gpt_tiny
from paddle_tpu.ops.sampling import row_key, sample_rows
from paddle_tpu.resilience.chaos import (Fault, FaultPlan,
                                         SERVING_FAULT_KINDS,
                                         ServingFaultInjector)
from paddle_tpu.resilience import plangen
from paddle_tpu.serving import (RejectReason, RejectedRequest,
                                Request, ServeConfig, ServingEngine,
                                request_seed)
from paddle_tpu.serving.frontend import ServingFrontend
from paddle_tpu.serving.router import (FleetFrontend, FleetRouter,
                                       ReplicaHandle, ReplicaDied)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _tiny_model(**kw):
    kw.setdefault('num_layers', 2)
    kw.setdefault('hidden_size', 32)
    kw.setdefault('num_heads', 2)
    kw.setdefault('max_seq_len', 64)
    paddle.seed(7)
    m = gpt_tiny(**kw)
    m.eval()
    return m


def _tiny_config(**kw):
    kw.setdefault('block_size', 4)
    kw.setdefault('max_slots', 4)
    kw.setdefault('decode_span', 2)
    kw.setdefault('prompt_buckets', (4, 8))
    kw.setdefault('batch_buckets', (1, 2, 4))
    kw.setdefault('prefill_batch', 2)
    kw.setdefault('max_model_len', 32)
    kw.setdefault('temperature', 0.0)
    return ServeConfig(**kw)


def _sampled_config(**kw):
    kw.setdefault('temperature', 0.8)
    kw.setdefault('top_k', 8)
    kw.setdefault('seed', 11)
    return _tiny_config(**kw)


def _specs(n, seed=0, lo=3, hi=8, new_lo=3, new_hi=7):
    rs = np.random.RandomState(seed)
    return [(rs.randint(0, 128, (int(rs.randint(lo, hi)),))
             .astype('int64'), int(rs.randint(new_lo, new_hi)))
            for _ in range(n)]


def _read_sse(resp):
    """Parsed SSE events until the terminal {'done': ...} record."""
    events = []
    while True:
        line = resp.readline()
        if not line:
            return events, None
        line = line.strip()
        if not line.startswith(b'data: '):
            continue
        ev = json.loads(line[len(b'data: '):])
        if ev.get('done'):
            return events, ev
        events.append(ev)


# =============================================================================
# per-request positional sampling keys
# =============================================================================

class TestSamplingKeys:
    def test_row_key_distinct_per_position_and_row(self):
        import jax
        base = jax.random.PRNGKey(5)
        seen = {tuple(np.asarray(row_key(base, pos, row)))
                for pos in range(4) for row in range(3)}
        assert len(seen) == 12          # every (pos, row) distinct
        again = tuple(np.asarray(row_key(base, 2, 1)))
        assert again in seen            # and deterministic

    def test_sample_rows_composes_row_keys(self):
        """Row r of a batched draw is exactly sample_token under
        row_key(base, pos, r) — generate's batch rows and the
        engine's per-request row-0 draws share one key algebra."""
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.sampling import sample_token
        rs = np.random.RandomState(1)
        logits = jnp.asarray(rs.randn(3, 64), jnp.float32)
        base = jax.random.PRNGKey(9)
        full = sample_rows(logits, base, 6, temperature=0.7, top_k=8)
        for r in range(3):
            solo = sample_token(logits[r], row_key(base, 6, r),
                                temperature=0.7, top_k=8)
            assert int(full[r]) == int(solo)

    @pytest.mark.parametrize('temperature', [0.0, 0.8])
    def test_engine_parity_vs_generate_greedy_and_sampled(
            self, temperature):
        """The whole point of the key discipline: a request streamed
        through the continuously-batching engine equals sequential
        batch-1 generate — at temperature 0 AND temperature>0."""
        m = _tiny_model()
        cfg = _sampled_config(temperature=temperature)
        eng = ServingEngine(m, cfg)
        reqs = [eng.submit(p, n) for p, n in _specs(6, seed=2)]
        rep = eng.run()
        assert rep['audit'] == []
        for req in reqs:
            assert req.state == Request.DONE, (req.rid, req.reason)
            out = m.generate(
                paddle.to_tensor(req.prompt[None, :]),
                max_new_tokens=req.max_new_tokens,
                temperature=temperature, top_k=cfg.top_k,
                seed=request_seed(req.rid, cfg.seed))
            ref = np.asarray(out.value)[0, req.prompt.size:].tolist()
            assert req.tokens == ref, req.rid

    def test_emitted_prefix_replay_resumes_bit_exact(self):
        """The router's retry primitive: prompt + first-k emitted
        tokens with the SAME rid continues the stream bit-exactly
        (tokens land at identical absolute positions, so identical
        keys)."""
        m = _tiny_model()
        eng = ServingEngine(m, _sampled_config())
        prompt = np.asarray([2, 7, 1, 8], 'int64')
        req = eng.submit(prompt, 8)
        eng.run()
        assert req.state == Request.DONE and len(req.tokens) == 8
        k = 3                   # replay stays inside bucket 8
        resumed = ServingEngine(_tiny_model(), _sampled_config())
        replay = np.concatenate(
            [prompt, np.asarray(req.tokens[:k], 'int64')])
        r2 = Request(req.rid, replay, max_new_tokens=8 - k)
        resumed.submit(r2)
        resumed.run()
        assert r2.tokens == req.tokens[k:]


# =============================================================================
# typed admission + cancel rollback
# =============================================================================

class TestTypedAdmission:
    def test_exceeds_pool_is_typed_and_evented(self):
        telemetry.reset()
        eng = ServingEngine(_tiny_model(), _tiny_config())
        with pytest.raises(RejectedRequest) as ei:
            eng.submit(np.arange(8).astype('int64'), 30)
        assert ei.value.reason == RejectReason.EXCEEDS_POOL
        assert ei.value.http_status == 413
        assert isinstance(ei.value, ValueError)   # old callers hold
        evs = telemetry.events('serve_reject')
        assert evs and evs[-1]['reason'] == RejectReason.EXCEEDS_POOL

    def test_reason_taxonomy_and_statuses(self):
        assert set(RejectReason.ALL) == {
            RejectReason.EXCEEDS_POOL, RejectReason.QUEUE_FULL,
            RejectReason.DRAINING}
        assert RejectReason.HTTP_STATUS[RejectReason.EXCEEDS_POOL] \
            == 413
        assert RejectReason.HTTP_STATUS[RejectReason.QUEUE_FULL] == 429
        assert RejectReason.HTTP_STATUS[RejectReason.DRAINING] == 503
        with pytest.raises(AssertionError):
            RejectedRequest('not_a_reason', 'x')

    def test_cancel_rolls_back_token_accounting(self):
        eng = ServingEngine(_tiny_model(), _tiny_config())
        req = eng.submit(np.arange(4).astype('int64'), 12)
        while len(req.tokens) < 2:
            eng.step()
        emitted = len(req.tokens)
        before = eng.decoded_tokens
        assert eng.cancel(req.rid, cause='client_disconnect')
        assert req.state == Request.EVICTED
        assert req.reason == 'client_disconnect'
        assert eng.decoded_tokens == before - emitted
        assert not eng.cancel('no-such-rid')
        # pool fully reclaimed: a fresh request still runs to DONE
        r2 = eng.submit(np.arange(4).astype('int64'), 3)
        eng.run()
        assert r2.state == Request.DONE


# =============================================================================
# the HTTP door (in-process frontend)
# =============================================================================

@pytest.fixture
def door():
    eng = ServingEngine(_tiny_model(), _sampled_config())
    fe = ServingFrontend(eng, port=0).start()
    yield fe
    fe.stop()


def _post(port, path, doc=None, timeout=30):
    c = http.client.HTTPConnection('127.0.0.1', port, timeout=timeout)
    c.request('POST', path,
              body=json.dumps(doc) if doc is not None else '',
              headers={'Content-Type': 'application/json'})
    r = c.getresponse()
    body = json.loads(r.read().decode())
    c.close()
    return r.status, dict(r.getheaders()), body


class TestFrontendDoor:
    def test_healthz_status_and_nonstream_generate(self, door):
        c = http.client.HTTPConnection('127.0.0.1', door.port,
                                       timeout=10)
        c.request('GET', '/healthz')
        assert json.loads(c.getresponse().read())['ok'] is True
        c.close()
        st, _h, body = _post(door.port, '/v1/generate', {
            'prompt': [3, 1, 4, 1], 'max_new_tokens': 5,
            'rid': 'nd-0', 'stream': False})
        assert st == 200 and body['state'] == 'done'
        assert len(body['tokens']) == 5
        c = http.client.HTTPConnection('127.0.0.1', door.port,
                                       timeout=10)
        c.request('GET', '/status.json')
        doc = json.loads(c.getresponse().read())
        c.close()
        for key in ('queue_depth', 'kv_occupancy', 'shed_counts',
                    'alerts', 'max_slots', 'retry_after_s'):
            assert key in doc, key
        assert doc['shed_counts'] == {r: 0 for r in RejectReason.ALL}

    def test_sse_stream_matches_engine_semantics(self, door):
        c = http.client.HTTPConnection('127.0.0.1', door.port,
                                       timeout=30)
        c.request('POST', '/v1/generate', body=json.dumps(
            {'prompt': [9, 2, 5, 1, 7], 'max_new_tokens': 6,
             'rid': 'st-0'}),
            headers={'Content-Type': 'application/json'})
        r = c.getresponse()
        assert r.status == 200
        events, done = _read_sse(r)
        c.close()
        assert [e['i'] for e in events] == list(range(6))
        assert done['state'] == 'done' and done['n'] == 6
        # the streamed tokens ARE the engine's request record
        req = door._requests['st-0']
        assert [e['token'] for e in events] == list(req.tokens)

    def test_the_loop_paces_by_the_engine_and_leaves_nothing_in_flight(
            self, door):
        """PR 36: the engine thread asks `wait_s` before each
        intervention (the door is open, so an arrival may always
        come) and reads the last span when the schedule empties: the
        tokens are the engine's own, nothing stays in flight, the
        pool comes back whole."""
        import concurrent.futures
        eng = door.engine
        asked = []
        wait_s = eng.wait_s
        eng.wait_s = lambda: asked.append(wait_s()) or asked[-1]
        docs = [{'prompt': [2 + i, 7, 1, 8], 'max_new_tokens': 5 + i,
                 'rid': f'pace-{i}', 'stream': False} for i in range(5)]
        with concurrent.futures.ThreadPoolExecutor(5) as pool:
            replies = list(pool.map(
                lambda d: _post(door.port, '/v1/generate', d), docs))
        for doc, (st, _h, body) in zip(docs, replies):
            assert st == 200 and body['state'] == 'done'
            assert len(body['tokens']) == doc['max_new_tokens']
        deadline = time.monotonic() + 10
        while eng._in_flight is not None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert asked and eng._in_flight is None
        assert eng.scheduler.audit() == []
        assert eng.cache.free_blocks == eng.cache.num_blocks - 1
        assert eng.decoded_tokens == sum(d['max_new_tokens'] for d in docs)
        assert eng.counts()['decode_dispatches_ahead'] >= 1

    def test_typed_sheds_with_retry_after(self, door):
        # 413 exceeds_pool straight through the door
        st, hdrs, body = _post(door.port, '/v1/generate', {
            'prompt': list(range(8)), 'max_new_tokens': 30,
            'rid': 'big-0'})
        assert st == 413
        assert body['error'] == RejectReason.EXCEEDS_POOL
        assert float(hdrs['Retry-After']) > 0
        # draining: every new request is a typed 503
        st, _h, _b = _post(door.port, '/admin/drain')
        assert st == 200
        st, hdrs, body = _post(door.port, '/v1/generate', {
            'prompt': [1, 2, 3], 'max_new_tokens': 2, 'rid': 'dr-x'})
        assert st == 503
        assert body['error'] == RejectReason.DRAINING
        assert 'Retry-After' in hdrs
        assert door.shed_counts[RejectReason.DRAINING] == 1

    def test_queue_full_sheds_when_admission_queue_bounded(self):
        eng = ServingEngine(_tiny_model(), _tiny_config())
        fe = ServingFrontend(eng, port=0, max_queue=0).start()
        try:
            st, _h, body = _post(fe.port, '/v1/generate', {
                'prompt': [1, 2, 3], 'max_new_tokens': 2,
                'rid': 'q-0'})
            assert st == 429
            assert body['error'] == RejectReason.QUEUE_FULL
            assert fe.shed_counts[RejectReason.QUEUE_FULL] == 1
        finally:
            fe.stop()

    def test_client_disconnect_evicts_and_rolls_back(self):
        # a stream long enough that the client is provably gone while
        # the engine still decodes (a short one finishes before the
        # dead socket's RST can surface — and 'done' is then correct)
        model = _tiny_model(max_seq_len=512)
        cfg = _sampled_config(max_model_len=320, num_blocks=96,
                              prompt_buckets=(4,), max_slots=2,
                              batch_buckets=(1, 2))
        fe = ServingFrontend(ServingEngine(model, cfg),
                             port=0).start()
        try:
            c = http.client.HTTPConnection('127.0.0.1', fe.port,
                                           timeout=30)
            c.request('POST', '/v1/generate', body=json.dumps(
                {'prompt': [4, 4, 4, 4], 'max_new_tokens': 300,
                 'rid': 'cd-0'}),
                headers={'Content-Type': 'application/json'})
            r = c.getresponse()
            seen = 0
            while seen < 2:             # stream is live, then vanish
                line = r.readline().strip()
                if line.startswith(b'data: '):
                    seen += 1
            # http.client reads through a makefile() object that keeps
            # the fd alive — close it too or no FIN ever reaches the
            # server and the disconnect is undetectable
            r.fp.close()
            c.sock.close()
            req = fe._requests['cd-0']
            deadline = time.monotonic() + 60
            while not req.done and time.monotonic() < deadline:
                time.sleep(0.02)
            assert req.state == Request.EVICTED
            assert req.reason == 'client_disconnect'
            assert len(req.tokens) < 300    # evicted mid-decode
        finally:
            fe.stop()

    def test_forced_alert_latch_shows_in_status(self, door):
        st, _h, body = _post(door.port, '/admin/alert/slo_breach')
        assert st == 200 and 'slo_breach' in body['alerts']
        assert 'slo_breach' in door.alerts()


# =============================================================================
# the router: dispatch, retry, drain/promote, ledger invariants
# =============================================================================

class _ScriptedReplica(ThreadingHTTPServer):
    """A minimal fake replica: /status.json from a dict, streams a
    scripted token list and then — if told to — drops the connection
    without a terminal event (a dying replica, reproduced to the
    byte), or 429s every generate (an overloaded one)."""

    def __init__(self, status=None, tokens=(), die_after=None,
                 reject=False):
        super().__init__(('127.0.0.1', 0), _ScriptedHandler)
        self.daemon_threads = True
        self.status_doc = dict(status or {})
        self.status_doc.setdefault('ok', True)
        self.tokens = list(tokens)
        self.die_after = die_after
        self.reject = reject
        self.hits = 0
        threading.Thread(target=self.serve_forever,
                         daemon=True).start()

    def handle(self):                   # ReplicaHandle duck-typing
        return ReplicaHandle.attach(
            f'fake:{self.server_address[1]}',
            f'http://127.0.0.1:{self.server_address[1]}')


class _ScriptedHandler(BaseHTTPRequestHandler):
    protocol_version = 'HTTP/1.1'

    def log_message(self, *args):
        pass

    def do_GET(self):                   # noqa: N802
        doc = (self.server.status_doc if self.path == '/status.json'
               else {'ok': True})
        data = json.dumps(doc).encode()
        self.send_response(200)
        self.send_header('Content-Length', str(len(data)))
        self.end_headers()
        self.wfile.write(data)

    def do_POST(self):                  # noqa: N802
        srv = self.server
        srv.hits += 1
        n = int(self.headers.get('Content-Length') or 0)
        self.rfile.read(n)
        if srv.reject:
            data = json.dumps({'error': RejectReason.QUEUE_FULL,
                               'detail': 'scripted',
                               'retry_after_s': 0.05}).encode()
            self.send_response(429)
            self.send_header('Content-Length', str(len(data)))
            self.end_headers()
            self.wfile.write(data)
            return
        self.send_response(200)
        self.send_header('Transfer-Encoding', 'chunked')
        self.end_headers()
        emit = srv.tokens if srv.die_after is None \
            else srv.tokens[:srv.die_after]
        for i, tok in enumerate(emit):
            data = b'data: ' + json.dumps(
                {'i': i, 'token': int(tok)}).encode() + b'\n\n'
            self.wfile.write(b'%X\r\n%s\r\n' % (len(data), data))
            self.wfile.flush()
        if srv.die_after is not None:
            self.wfile.flush()
            self.connection.close()     # mid-stream death
            return
        data = b'data: ' + json.dumps(
            {'done': True, 'state': 'done',
             'reason': 'max_tokens'}).encode() + b'\n\n'
        self.wfile.write(b'%X\r\n%s\r\n' % (len(data), data))
        self.wfile.write(b'0\r\n\r\n')


@pytest.fixture
def real_replica():
    eng = ServingEngine(_tiny_model(), _sampled_config())
    fe = ServingFrontend(eng, port=0).start()
    handle = ReplicaHandle.attach('real', fe.url)
    yield handle, eng
    fe.stop()


class TestFleetRouter:
    def test_dispatch_prefers_low_load(self, real_replica):
        handle, _eng = real_replica
        busy = _ScriptedReplica(status={'kv_occupancy': 0.9,
                                        'queue_depth': 7,
                                        'max_queue': 8, 'live': 4,
                                        'max_slots': 4})
        try:
            router = FleetRouter([busy.handle(), handle])
            assert router.pick().name == 'real'
        finally:
            busy.shutdown()

    def test_midstream_death_retries_bit_exact(self, real_replica):
        """A replica that streamed 3 tokens and died: the survivor
        must continue from offset 3 and the JOINED stream must equal
        the single-engine reference — plus a 'retry' fleet event and
        a clean ledger."""
        handle, _eng = real_replica
        telemetry.reset()
        m = _tiny_model()
        cfg = _sampled_config()
        prompt, n = list(range(1, 6)), 8
        out = m.generate(
            paddle.to_tensor(np.asarray(prompt, 'int64')[None, :]),
            max_new_tokens=n, temperature=cfg.temperature,
            top_k=cfg.top_k, seed=request_seed('rt-0', cfg.seed))
        ref = np.asarray(out.value)[0, len(prompt):].tolist()
        dying = _ScriptedReplica(
            status={'kv_occupancy': 0.0, 'queue_depth': 0,
                    'max_queue': 8, 'live': 0, 'max_slots': 4},
            tokens=ref, die_after=3)
        try:
            router = FleetRouter([dying.handle(), handle])
            delivered = []
            entry = router.generate(
                prompt, n, 'rt-0',
                on_token=lambda i, t: delivered.append((i, t)))
            assert entry['state'] == 'finished'
            assert entry['retried'] == 1
            assert entry['tokens'] == ref
            # at-most-once: offsets delivered exactly once, in order
            assert [i for i, _ in delivered] == list(range(n))
            assert [t for _, t in delivered] == ref
            assert any(e['action'] == 'retry' for e in router.events)
            assert telemetry.events('fleet_event')
            assert router.check_invariants() == []
        finally:
            dying.shutdown()

    def test_rejection_exhausts_typed_never_silent(self):
        full = _ScriptedReplica(
            status={'kv_occupancy': 0.0, 'queue_depth': 0,
                    'max_queue': 8, 'live': 0, 'max_slots': 4},
            reject=True)
        try:
            router = FleetRouter([full.handle()], max_attempts=2)
            entry = router.generate([1, 2, 3], 4, 'rj-0')
            assert entry['state'] == 'rejected'
            assert entry['reason'] == RejectReason.QUEUE_FULL
            assert router.check_invariants() == []
        finally:
            full.shutdown()

    def test_forced_alert_drains_and_promotes_spare(self, real_replica):
        handle, _eng = real_replica
        spare = _ScriptedReplica(
            status={'kv_occupancy': 0.0, 'queue_depth': 0,
                    'in_flight': 0})
        try:
            router = FleetRouter([handle], spares=[spare.handle()])
            # latch the alert through the drill seam, then tick
            st, _h, body = _post(handle.port,
                                 '/admin/alert/memory_pressure')
            assert st == 200
            router.health_tick()
            assert handle.draining
            actions = [e['action'] for e in router.events]
            assert 'drain' in actions and 'promote' in actions
            assert router.dispatchable()      # spare took over
        finally:
            spare.shutdown()

    def test_fleet_frontend_door_and_duplicate_rid(self, real_replica):
        handle, _eng = real_replica
        router = FleetRouter([handle])
        fleet = FleetFrontend(router, port=0).start()
        try:
            st, _h, body = _post(fleet.port, '/v1/generate', {
                'prompt': [2, 4, 6], 'max_new_tokens': 4,
                'rid': 'fd-0', 'stream': False})
            assert st == 200 and body['state'] == 'finished'
            assert len(body['tokens']) == 4
            # same rid again: the ledger refuses a second life
            st, _h, body = _post(fleet.port, '/v1/generate', {
                'prompt': [2, 4, 6], 'max_new_tokens': 4,
                'rid': 'fd-0', 'stream': False})
            assert st == 400
            st, _h, body = _post(fleet.port, '/v1/cancel/nope')
            assert st == 404
            assert router.check_invariants() == []
        finally:
            fleet.stop()


# slow: spins three worker interpreters (tools/serve_fleet.py);
# nothing automatic runs it.
@pytest.mark.slow
@pytest.mark.faultinject
class TestRealFleetE2E:
    """The fleet tests above script one side of each failure; here two
    real replicas and a warm spare run behind the router."""

    DOC = {'model': 'tiny',
           'model_kwargs': {'num_layers': 2, 'num_heads': 2,
                            'hidden_size': 32, 'vocab_size': 128,
                            'max_seq_len': 128},
           'block_size': 8, 'max_slots': 4, 'decode_span': 4,
           'num_blocks': 64, 'temperature': 0.7, 'top_k': 8,
           'seed': 13}

    def test_overload_clean_twin_kill_and_drain(self, tmp_path):
        import random
        import signal
        import sys
        sys.path.insert(0, os.path.join(_REPO, 'tools'))
        try:
            import serve_fleet
        finally:
            sys.path.pop(0)
        config_path = str(tmp_path / 'serve.json')
        with open(config_path, 'w') as f:
            json.dump(self.DOC, f)

        def shape(rid):
            r = random.Random(rid)
            return ([r.randrange(1, 120)
                     for _ in range(r.randrange(4, 9))],
                    r.randrange(6, 10))

        def run_many(router, rids, pace_s=0.002, on_token=None):
            results, threads = {}, []

            def one(rid):
                prompt, n = shape(rid)
                try:
                    results[rid] = router.generate(
                        prompt, n, rid,
                        on_token=(None if on_token is None else
                                  (lambda i, t: on_token(rid, i, t))))
                except Exception as e:      # a crash IS the finding
                    results[rid] = {'state': 'crashed',
                                    'reason': repr(e)[:120]}
            for rid in rids:
                t = threading.Thread(target=one, args=(rid,),
                                     daemon=True)
                t.start()
                threads.append(t)
                time.sleep(pace_s)
            for t in threads:
                t.join(timeout=120)
            assert not [t for t in threads if t.is_alive()]
            return results

        def shed_total(router):
            n = 0
            for rep in router.replicas + router.spares:
                if rep.alive():
                    try:
                        n += sum((rep.status(timeout_s=2.0)
                                  .get('shed_counts') or {}).values())
                    except OSError:
                        pass
            return n

        def reference(rids):
            """Each rid alone through a fresh single engine."""
            eng = serve_fleet.build_engine(self.DOC)
            out = {}
            for rid in rids:
                prompt, n = shape(rid)
                r = Request(rid, prompt, max_new_tokens=n)
                eng.submit(r)
                eng.run()
                out[rid] = [int(t) for t in r.tokens]
            return out

        router = serve_fleet.launch_fleet(
            config_path, replicas=2, spares=1,
            workdir=str(tmp_path / 'fleet'))
        try:
            # overload: a burst far over pool and queue comes back
            # with TYPED rejections only; nothing lost, nobody dies
            res = run_many(router, [f'ov-{i}' for i in range(24)])
            states = {r['state'] for r in res.values()}
            assert not states & {'crashed', 'failed', 'in_flight'}, res
            assert all(r['reason'] in RejectReason.ALL
                       for r in res.values()
                       if r['state'] == 'rejected'), res
            assert shed_total(router) > 0
            assert sum(r.alive() for r in router.replicas) == 2
            assert router.check_invariants() == []

            # the same shapes, gently paced: nothing shed, every
            # stream bit-exact against a single engine
            shed0 = shed_total(router)
            rids = [f'cl-{i}' for i in range(4)]
            res = run_many(router, rids, pace_s=0.4)
            want = reference(rids)
            assert [res[r]['state'] for r in rids] == ['finished'] * 4
            assert shed_total(router) == shed0
            assert {r: res[r]['tokens'] for r in rids} == want
            assert router.check_invariants() == []

            # a seeded SIGKILL of the serving replica mid-stream:
            # every in-flight rid finishes, retried on the survivor,
            # streams still bit-exact, the warm spare promoted
            inj = ServingFaultInjector(FaultPlan(seed=0, faults=[
                Fault('replica_kill', after_tokens=3, count=1)]))
            kill_lock = threading.Lock()

            def tap(rid, i, tok):
                with kill_lock:
                    fired = inj.fleet_faults(rid, i + 1)
                for _f in fired:
                    victim = router.replica(
                        router.ledger[rid]['replicas'][-1])
                    if victim is not None:
                        victim.kill(signal.SIGKILL)

            rids = [f'ki-{i}' for i in range(3)]
            res = run_many(router, rids, pace_s=0.05, on_token=tap)
            want = reference(rids)
            assert inj.injected
            assert [res[r]['state'] for r in rids] == ['finished'] * 3
            assert sum(res[r].get('retried', 0) for r in rids) >= 1
            assert {r: res[r]['tokens'] for r in rids} == want
            assert [e for e in router.events
                    if e['action'] == 'promote']
            assert router.check_invariants() == []

            # a forced slo_breach latch on the replica that holds a
            # stream: it drains, the stream loses no token, the fleet
            # keeps serving
            out = {}
            t = threading.Thread(
                target=lambda: out.update(entry=router.generate(
                    *shape('dr-0'), 'dr-0')), daemon=True)
            t.start()
            deadline = time.time() + 60
            while time.time() < deadline and not (
                    router.ledger.get('dr-0') or {}).get('replicas'):
                time.sleep(0.01)
            owner = router.replica(
                router.ledger['dr-0']['replicas'][-1])
            owner.post_json('/admin/alert/slo_breach')
            router.health_tick()
            t.join(timeout=120)
            assert not t.is_alive()
            assert owner.draining
            assert out['entry']['state'] == 'finished'
            assert out['entry']['tokens'] == reference(['dr-0'])['dr-0']
            assert router.dispatchable()
            assert [e for e in router.events if e['action'] == 'drain']
            assert router.check_invariants() == []
        finally:
            router.stop()


# =============================================================================
# serving chaos kinds
# =============================================================================

class TestServingChaosKinds:
    def test_kinds_declared_optin_and_schema_stable(self):
        from paddle_tpu.resilience.chaos import FAULT_KINDS
        assert set(SERVING_FAULT_KINDS) == {
            'replica_kill', 'replica_hang', 'client_disconnect',
            'slow_client'}
        for k in SERVING_FAULT_KINDS:
            assert k in FAULT_KINDS
            assert k in plangen.OPTIN_KINDS
            assert k not in plangen.GENERATABLE_KINDS   # draw stream
        # after_tokens omitted when unset: pre-existing plans keep
        # their canonical JSON (and golden fingerprints)
        assert 'after_tokens' not in Fault('sigkill',
                                           at_step=3).to_dict()
        d = Fault('replica_kill', after_tokens=4, count=1).to_dict()
        assert Fault.from_dict(d).after_tokens == 4

    def test_legality_rules(self):
        ok = Fault('replica_kill', after_tokens=3, count=1, rank=1)
        assert plangen.legal(ok, steps=10, procs=2)
        assert not plangen.legal(
            Fault('replica_kill', count=1), 10, 2)       # no clock
        assert not plangen.legal(
            Fault('replica_hang', after_tokens=2), 10, 2)  # unbounded
        assert not plangen.legal(
            Fault('replica_kill', after_tokens=2, count=1, rank=9),
            10, 2)                                       # no replica
        assert plangen.legal(
            Fault('slow_client', after_tokens=0, count=1,
                  delay_s=0.5), 10, 1)

    def test_injector_fires_once_with_filters(self):
        telemetry.reset()
        plan = FaultPlan(seed=0, faults=[
            Fault('replica_kill', after_tokens=3, count=1, rank=0),
            Fault('client_disconnect', after_tokens=2, count=1,
                  path='cd-'),
        ])
        inj = ServingFaultInjector(plan, telemetry=telemetry)
        assert not inj.fleet_faults('r-1', 2, replica_index=0)
        assert not inj.fleet_faults('r-1', 3, replica_index=1)
        hit = inj.fleet_faults('r-1', 3, replica_index=0)
        assert [f.kind for f in hit] == ['replica_kill']
        assert not inj.fleet_faults('r-1', 4, replica_index=0)
        assert not inj.client_faults('other', 9)     # path filter
        assert [f.kind for f in inj.client_faults('cd-7', 2)] \
            == ['client_disconnect']
        assert [e['fault'] for e in inj.injected] \
            == ['replica_kill', 'client_disconnect']
        assert len(telemetry.events('fault_injected')) == 2


# =============================================================================
# tp>1 sharded pool
# =============================================================================

class TestShardedPoolTP2:
    def test_tp2_bitexact_vs_tp1_audit_clean(self):
        """dp1xtp2 virtual CPU mesh: the paged pool shards its head
        axis over 'tp' (POOL_SPEC) and every sampled stream stays
        bit-exact vs the unsharded engine, audit clean."""
        import jax
        from paddle_tpu.distributed import env as dist_env
        if len(jax.devices()) < 2:
            pytest.skip('needs >=2 virtual devices')
        specs = _specs(5, seed=3)

        def run(mesh_axes):
            prev = dist_env.get_mesh()
            if mesh_axes:
                dist_env.set_mesh(dist_env.build_mesh(mesh_axes))
            try:
                eng = ServingEngine(_tiny_model(), _sampled_config())
                reqs = [eng.submit(p, n) for p, n in specs]
                rep = eng.run()
                return ([list(r.tokens) for r in reqs], rep['audit'],
                        eng)
            finally:
                dist_env.set_mesh(prev)

        t1, audit1, _ = run(None)
        t2, audit2, eng2 = run({'dp': 1, 'tp': 2})
        assert audit1 == [] and audit2 == []
        assert t1 == t2
        # the pool is genuinely sharded, not replicated: its head
        # axis, the third of [blocks, positions, heads, head_dim],
        # rides 'tp'
        k0 = eng2.cache.pools[0][0]
        spec = getattr(k0.sharding, 'spec', None)
        assert spec is not None and tuple(spec)[:3] == (None, None, 'tp'), \
            spec
        nb, bs, nh, hd = k0.shape
        assert {s.data.shape for s in k0.addressable_shards} \
            == {(nb, bs, nh // 2, hd)}


# =============================================================================
# run_report consumption
# =============================================================================

class TestRunReportServing:
    def test_serve_reject_and_fleet_event_render(self):
        import sys
        sys.path.insert(0, os.path.join(_REPO, 'tools'))
        try:
            import run_report
        finally:
            sys.path.pop(0)
        events = [
            {'kind': 'serve_reject', 'rid': 'a', 'ts': 1.0,
             'reason': 'queue_full', 'retry_after_s': 0.2},
            {'kind': 'serve_reject', 'rid': 'b', 'ts': 1.1,
             'reason': 'queue_full', 'retry_after_s': 0.2},
            {'kind': 'serve_reject', 'rid': 'c', 'ts': 1.2,
             'reason': 'exceeds_pool', 'retry_after_s': 0.1},
            {'kind': 'fleet_event', 'ts': 2.0, 'action': 'retry',
             'rid': 'd', 'replica': 'r1', 'offset': 3},
            {'kind': 'fleet_event', 'ts': 2.1, 'action': 'drain',
             'replica': 'r0', 'cause': 'slo_breach'},
            {'kind': 'fleet_event', 'ts': 2.2, 'action': 'promote',
             'replica': 's0'},
        ]
        rep = run_report.analyze(events, sources=[])
        sv = rep['serving']
        assert sv['rejected'] == 3
        assert sv['shed_by_reason'] == {'queue_full': 2,
                                        'exceeds_pool': 1}
        assert sv['fleet']['by_action'] == {'retry': 1, 'drain': 1,
                                            'promote': 1}
        assert sv['fleet']['timeline'][0]['offset'] == 3
        import io
        buf = io.StringIO()
        run_report.render(rep, stream=buf)
        text = buf.getvalue()
        assert 'shed at admission' in text
        assert 'fleet: 3 control event(s)' in text
