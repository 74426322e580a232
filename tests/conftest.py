"""Test config: force an 8-device virtual CPU mesh BEFORE jax imports.

Mirrors the reference's multi-device unittests
(/root/reference/python/paddle/fluid/tests/unittests/test_collective_*)
which launch multi-process NCCL groups; here XLA gives us N virtual
devices in one process.
"""
import os

# force (not setdefault): a TPU machine sets JAX_PLATFORMS for the chip;
# unit tests must run on the virtual 8-device CPU
os.environ['JAX_PLATFORMS'] = 'cpu'
flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in flags:
    os.environ['XLA_FLAGS'] = (
        flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ.setdefault('JAX_ENABLE_X64', '0')
# jax's persistent compile cache is ON for real runs (the exec/text
# tiers are opt-in); tier-1 runs with everything OFF so test timing
# and behavior stay cache-independent (and a warm .jax_cache can never
# mask a recompile regression).  Cache-behavior tests opt back in with
# monkeypatch / subprocess envs.
os.environ.setdefault('PADDLE_TPU_COMPILE_CACHE', '0')
# same hermeticity for the sampled profiler: an ambient
# PADDLE_TPU_PROFILE would make every fit/trainer test open
# jax.profiler windows (block_until_ready + trace parse per close) —
# profile-behavior tests pass profile= / monkeypatch explicitly
os.environ.setdefault('PADDLE_TPU_PROFILE', '0')
# ...and for the straggler/hang watchdog: an ambient
# PADDLE_TPU_WATCHDOG would arm deadline supervision (and its
# escalation exits!) under every trainer test — watchdog-behavior
# tests pass watchdog= / monkeypatch explicitly
os.environ.setdefault('PADDLE_TPU_WATCHDOG', '0')
# ...and for the fused K-step loop: an ambient PADDLE_TPU_FUSED_STEPS
# would flip every fit() into chunked dispatch (different callback /
# sync cadence than the tests pin) — fused-behavior tests pass
# fused_steps= explicitly
os.environ.setdefault('PADDLE_TPU_FUSED_STEPS', '0')
# ...and for the quantized collective wire: an ambient
# PADDLE_TPU_QUANT_COLLECTIVES would re-route every dp trainer's grad
# sync through the int8 decomposition (different numerics than the
# exactness tests pin) — quant-behavior tests pass quant_collectives=
# explicitly
os.environ.setdefault('PADDLE_TPU_QUANT_COLLECTIVES', '0')
# ...and for the cluster observability plane: an ambient
# PADDLE_TPU_CLUSTER_STATS would subscribe a stats-frame publisher
# under every trainer test — cluster-obs tests pass cluster_stats= /
# construct publishers explicitly
os.environ.setdefault('PADDLE_TPU_CLUSTER_STATS', '0')
# ...and for the self-healing plan supervisor: an ambient
# PADDLE_TPU_SUPERVISOR would subscribe an ACTUATOR to every test
# trainer's event stream (a stray drift_detected could queue a live
# plan swap mid-test) — supervisor-behavior tests pass supervisor= /
# construct PlanSupervisor explicitly
os.environ.setdefault('PADDLE_TPU_SUPERVISOR', '0')
# ...and for the runtime lock checker: an ambient PADDLE_TPU_LOCKCHECK
# would patch threading.Lock/RLock factories under every test (and
# first-armed-wins would make arming order test-order-dependent) —
# lockcheck-behavior tests arm install()/maybe_install(True) explicitly
os.environ.setdefault('PADDLE_TPU_LOCKCHECK', '0')
# ...and for the memory observatory: an ambient PADDLE_TPU_MEMSTATS
# would arm the live sampler thread plus the armed extraction paths
# (an extra lower().compile() per hapi/jit/serving module) under every
# test — memstats-behavior tests pass memstats= / monkeypatch
# explicitly
os.environ.setdefault('PADDLE_TPU_MEMSTATS', '0')

import jax  # noqa: E402

# this build's XLA CPU defaults to bf16-ish matmul precision; tests check
# f32 numerical parity, so force full precision (TPU perf paths pass bf16
# dtypes explicitly, which this setting does not affect)
jax.config.update('jax_default_matmul_precision', 'highest')


import pytest  # noqa: E402


@pytest.fixture
def chaos():
    """Factory for scoped ChaosEngines: ``eng = chaos(plan)`` patches
    the fault seams for the test body and ALWAYS unpatches at teardown
    (even on failure), so one test's injected faults can never leak
    into the next."""
    from paddle_tpu.resilience.chaos import ChaosEngine, FaultPlan
    engines = []

    def make(plan, heartbeat_file=None):
        if isinstance(plan, dict):
            plan = FaultPlan(**plan)
        eng = ChaosEngine(plan, heartbeat_file=heartbeat_file)
        engines.append(eng)
        return eng.activate()

    yield make
    # reverse order: a later engine saved the earlier one's patched
    # seams as its "originals", so forward teardown would re-install
    # the first engine's fault wrappers permanently
    for eng in reversed(engines):
        eng.deactivate()


def pytest_configure(config):
    config.addinivalue_line(
        'markers',
        'slow: long-running tests excluded from the tier-1 gate '
        "(-m 'not slow')")
    config.addinivalue_line(
        'markers',
        'faultinject: crash-recovery fault-injection tests (torn '
        'checkpoint dirs, SIGKILL mid-save, SIGTERM preemption, NaN '
        'rollback).  Tier-1-eligible — deliberately NOT slow: the '
        'recovery path must stay gated on every PR')
