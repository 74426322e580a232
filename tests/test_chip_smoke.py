"""CPU checks for the bring-up PR: chip_smoke.py refuses to run off the
chip, the compile cache is placed by one rule, fleet workers inherit
their platform, a dead engine thread closes the door, the smoke's
serve set-up is coherent, and the row-kernel gate is bounded by VMEM."""
import importlib.util
import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(relpath):
    name = os.path.basename(relpath).rsplit('.', 1)[0]
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, relpath))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ chip_smoke.py --
SMOKE = _load('chip_smoke.py')      # imports nothing but the stdlib


def _run_smoke(**env):
    return subprocess.run(
        [sys.executable, os.path.join(REPO, 'chip_smoke.py')],
        env=dict(os.environ, **env), cwd=REPO, capture_output=True,
        text=True, timeout=120)


def _no_result(proc):
    return not any(line.startswith('{"ok"')
                   for line in proc.stdout.splitlines())


def test_smoke_refuses_without_a_tpu():
    proc = _run_smoke(JAX_PLATFORMS='cpu')
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert _no_result(proc)


def test_smoke_refuses_interpret_mode():
    proc = _run_smoke(PADDLE_TPU_PALLAS_INTERPRET='1')
    assert proc.returncode != 0
    assert 'PADDLE_TPU_PALLAS_INTERPRET' in proc.stderr
    assert _no_result(proc)


def test_smoke_finds_named_kernels_in_compiled_hlo():
    hlo = (
        '%flash_fwd.1 = (bf16[96,1024,64]) custom-call(%a, %b), '
        'custom_call_target="tpu_custom_call", metadata={op_name='
        '"jit(step)/jvp(flash_fwd)/pallas_call" stack_frame_id=7}\n'
        '%x = f32[8] custom-call(%c), custom_call_target="tpu_custom_'
        'call", metadata={op_name="jit(step)/transpose(jvp(flash_bwd_'
        'dq))/pallas_call"}\n'
        '%y = f32[8] fusion(%c), metadata={op_name="layer_norm_fwd"}\n')
    assert SMOKE.has_kernel(hlo, 'flash_fwd')
    assert SMOKE.has_kernel(hlo, 'flash_bwd_dq')
    assert not SMOKE.has_kernel(hlo, 'flash_bwd_dkv')
    assert not SMOKE.has_kernel(hlo, 'layer_norm_fwd')   # not a call


# ----------------------------------------------------------- cache placement --
@pytest.fixture
def cache_env(monkeypatch):
    """compile_cache with a fresh placement memo and jax.config.update
    recorded instead of applied."""
    import jax
    from paddle_tpu.core import compile_cache as cc
    monkeypatch.setattr(cc, '_xla_applied', cc._NOT_APPLIED)
    monkeypatch.delenv(cc.ENV_VAR, raising=False)
    monkeypatch.delenv(cc.XLA_ENV_VAR, raising=False)
    updates = []
    monkeypatch.setattr(jax.config, 'update',
                        lambda k, v: updates.append((k, v)))
    return cc, updates


def test_cache_unset_is_checkout_jax_cache(cache_env):
    cc, updates = cache_env
    want = os.path.join(REPO, '.jax_cache')
    assert cc.setup_xla_cache() == want
    assert ('jax_compilation_cache_dir', want) in updates
    assert not cc.enabled()         # exec/text tiers are opt-in


def test_cache_env_set_is_left_to_jax(cache_env, monkeypatch, tmp_path):
    cc, updates = cache_env
    monkeypatch.setenv(cc.XLA_ENV_VAR, str(tmp_path / 'jc'))
    assert cc.setup_xla_cache() == str(tmp_path / 'jc')
    assert 'jax_compilation_cache_dir' not in [k for k, _ in updates]
    # nothing of the program's own is cached anywhere else
    assert cc.cache_dir() is None
    assert not cc.put_text('0' * 64, 'hlo')
    assert not os.path.exists(tmp_path / 'jc')     # jax writes, not us


def test_cache_tiers_serve_only_in_a_named_directory(cache_env,
                                                     monkeypatch,
                                                     tmp_path):
    cc, updates = cache_env
    monkeypatch.setenv(cc.ENV_VAR, str(tmp_path / 'tiers'))
    assert cc.cache_dir() == str(tmp_path / 'tiers')
    # jax's own cache stays where the rule puts it, not under the tiers
    assert ('jax_compilation_cache_dir',
            os.path.join(REPO, '.jax_cache')) in updates


def test_cache_switch_off_turns_jax_cache_off(cache_env, monkeypatch):
    cc, updates = cache_env
    monkeypatch.setenv(cc.ENV_VAR, '0')
    assert cc.setup_xla_cache() is None
    assert updates == [('jax_enable_compilation_cache', False)]


def test_only_compile_cache_places_the_jax_cache():
    hits = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith('.')
                   and d not in ('__pycache__', 'chiprun_out', 'tests')]
        for f in files:
            if f.endswith('.py'):
                with open(os.path.join(root, f)) as fh:
                    if 'jax_compilation_cache_dir' in fh.read():
                        hits.append(os.path.relpath(
                            os.path.join(root, f), REPO))
    assert hits == [os.path.join('paddle_tpu', 'core',
                                 'compile_cache.py')]


# ------------------------------------------------------------------- fleet --
@pytest.mark.parametrize('platform', ['tpu,cpu', None])
def test_replica_spawn_inherits_platform(monkeypatch, tmp_path, platform):
    from paddle_tpu.serving import router
    seen = {}

    class FakePopen:
        def __init__(self, cmd, env=None, **kw):
            seen['env'] = env

    monkeypatch.setattr(router.subprocess, 'Popen', FakePopen)
    if platform is None:
        monkeypatch.delenv('JAX_PLATFORMS', raising=False)
    else:
        monkeypatch.setenv('JAX_PLATFORMS', platform)
    router.ReplicaHandle.spawn('r0', 'serve.json', str(tmp_path))
    assert seen['env'].get('JAX_PLATFORMS') == platform
    assert REPO in seen['env']['PYTHONPATH']


# ---------------------------------------------------------------- frontend --
def _http(url, doc=None):
    """GET, or POST `doc` as JSON; returns (status, parsed body)."""
    req = urllib.request.Request(
        url, data=None if doc is None else json.dumps(doc).encode(),
        headers={'Content-Type': 'application/json'})
    try:
        with urllib.request.urlopen(req, timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_engine_thread_failure_closes_the_door(monkeypatch):
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_tiny
    from paddle_tpu.serving import ServeConfig, ServingEngine
    from paddle_tpu.serving.frontend import ServingFrontend

    paddle.seed(0)
    eng = ServingEngine(gpt_tiny(), ServeConfig(
        block_size=8, max_slots=4, decode_span=4, prompt_buckets=(8,),
        batch_buckets=(4,), prefill_batch=2, max_model_len=32))

    def boom():
        raise RuntimeError('decode module failed to compile')

    monkeypatch.setattr(eng, 'step', boom)
    fe = ServingFrontend(eng, port=0).start()
    try:
        assert _http(fe.url + '/healthz')[1]['ok']
        code, doc = _http(fe.url + '/v1/generate', {
            'prompt': [1, 2, 3], 'max_new_tokens': 4, 'stream': False})
        assert code == 200
        assert (doc['state'], doc['reason']) == ('evicted',
                                                 'engine_failed')
        code, health = _http(fe.url + '/healthz')
        assert code == 503 and not health['ok']
        assert 'failed to compile' in health['engine_error']
        code, doc = _http(fe.url + '/v1/generate', {
            'prompt': [1, 2, 3], 'max_new_tokens': 4, 'stream': False})
        assert code == 503 and doc['error'] == 'draining'
        assert not eng.scheduler.running and not eng.scheduler.queue
        assert not eng.scheduler.audit()
    finally:
        fe.stop()


# ------------------------------------------------------------ serve set-up --
# What leg_server checks only once it is on the chip, held here first.
@pytest.fixture(scope='module')
def serve_engine():
    from paddle_tpu.serving import ServingEngine
    model, cfg = SMOKE.serve_setup()
    return model, cfg, ServingEngine(model, cfg)


def test_serve_setup_builds_an_engine(serve_engine):
    model, cfg, eng = serve_engine
    assert not model.training
    assert eng.config is cfg and cfg.temperature == 0.0
    assert cfg.max_model_len <= model.config.max_seq_len
    assert cfg.batch_buckets == (8, 64) and cfg.max_slots == 64
    assert not eng.scheduler.audit()
    assert ({eng.prompt_bucket(n) for n in SMOKE.SERVE_PROMPT_LENS}
            == set(cfg.prompt_buckets))


@pytest.mark.parametrize('prompt_len', SMOKE.SERVE_PROMPT_LENS)
def test_serve_prompt_fits_bucket_and_model_length(serve_engine,
                                                   prompt_len):
    _model, cfg, eng = serve_engine
    assert eng.prompt_bucket(prompt_len) in cfg.prompt_buckets
    assert prompt_len + SMOKE.SERVE_NEW_TOKENS <= cfg.max_model_len


# -------------------------------------------------------------- kernel gate --
def test_row_kernel_gate_is_bounded_by_vmem():
    from paddle_tpu.ops._gating import pick_block_rows
    assert pick_block_rows(8192, 256, 768) == 256     # trainer LayerNorm
    assert pick_block_rows(8, 256, 768) == 8          # decode LayerNorm
    assert pick_block_rows(24576, 256, 256) == 256    # attention softmax
    assert pick_block_rows(64, 256, 32768) == 8       # 1 MiB block: fits
    assert pick_block_rows(256, 256, 50304) is None   # vocabulary row
    assert pick_block_rows(39, 256, 768) is None      # no 8-row divisor
