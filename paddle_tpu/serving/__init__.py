"""paddle_tpu.serving — the production inference runtime.

Continuous batching + paged KV-cache decode over the sharded engine
(ROADMAP item 1): a request scheduler that admits/evicts sequences at
every decode intervention, a paged KV cache (fixed-size blocks, one
preallocated pool, per-sequence block tables), a ragged paged
attention op (``ops/paged_attention.py``, RPA-style per PAPERS.md
arxiv 2604.15464) and a serving engine with fused multi-step decode —
all over a declared pow2 bucket set so ``tools/precompile.py --serve``
AOT-compiles the whole surface at deploy time.

    from paddle_tpu.serving import ServingEngine, ServeConfig
    eng = ServingEngine(model, ServeConfig(max_slots=64))
    eng.submit(prompt_ids, max_new_tokens=64)
    report = eng.run()

A model whose memory is one recurrent state a sequence
(``models/retention.py``: ``serving_state = 'recurrent'``) is served
by the same engine over a ``RecurrentStateCache``, and one whose layers
keep either a state or paged keys and values
(``models/granite_hybrid.py``: ``serving_state = 'hybrid'``) over a
``HybridCache``, a slot of state and paged blocks a sequence.

Additive: ``GPTForCausalLM.generate`` is unchanged (and bit-exact
with the engine's greedy decode by test).
"""
from .kv_cache import (                              # noqa: F401
    HybridCache, PagedKVCache, PagedCacheView, RecurrentStateCache,
    RecurrentStateView)
from .scheduler import (                             # noqa: F401
    ContinuousBatchingScheduler, DecodePlan, Request, RejectReason,
    RejectedRequest)
from .loadgen import poisson_requests                # noqa: F401
from .engine import (                                # noqa: F401
    DecodeAuditLayer, ServeConfig, ServingEngine, request_seed)

__all__ = ['PagedKVCache', 'PagedCacheView', 'RecurrentStateCache',
           'RecurrentStateView', 'HybridCache', 'Request', 'DecodePlan',
           'ContinuousBatchingScheduler', 'poisson_requests',
           'ServeConfig', 'ServingEngine', 'DecodeAuditLayer',
           'RejectReason', 'RejectedRequest', 'request_seed']
