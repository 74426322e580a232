"""Operations and bytes the hybrid Mamba-2 / attention decoder needs,
from shapes and counts alone, for the `.mamba` metrics' rooflines and
the whole window's share of the peak.  Only what the algorithm needs
counts: a live row's state read and written once a token step, the
visible positions' KV blocks of the attention layers, true prompt
tokens and never a bucket's padding.  What the program moves or
computes beyond that counts against it.  State and pool are float32
(the configuration's `state.dtype` and `kv_pool.dtype`), weights
bfloat16.  Kept with the benchmark, beside flops.py."""
from benchmark.flops import least_seconds, peaks  # noqa: F401

STATE_BYTES = 4
POOL_BYTES = 4


def layers_of(model):
    """(Mamba layers, attention layers) of the model as it is run."""
    kinds = list(model['layer_types'])
    return kinds.count('mamba'), kinds.count('attention')


def _ssm(model):
    return (int(model['mamba_n_heads']), int(model['mamba_d_head']),
            int(model['mamba_d_state']))


def ssm_decode_update(model):
    """(operations, bytes) of ONE live row's update in ONE Mamba layer:
    the state [N, H P] read and written once; x, B, C and dt read and y
    written; a scale and a rank-one add (3 operations an element) and
    the read-out by C (2 an element)."""
    H, P, N = _ssm(model)
    moved = STATE_BYTES * (2 * N * H * P + 2 * H * P + 2 * N + H)
    return 5 * N * H * P, moved


def token_weights(model):
    """Weights of every matrix ONE token meets in the whole decoder: the
    Mamba layers' in and out projections, the attention layers' q, k, v
    and o, every layer's MLP (the tied head is `head_weights`)."""
    h = int(model['hidden_size'])
    H, P, N = _ssm(model)
    inner = H * P
    mamba = h * (2 * inner + 2 * N + H) + inner * h
    d, hq, hkv = (int(model['head_dim']), int(model['num_heads']),
                  int(model['num_kv_heads']))
    attention = 2 * h * hq * d + 2 * h * hkv * d
    mlp = 3 * h * int(model['intermediate_size'])
    n_mamba, n_attn = layers_of(model)
    return n_mamba * mamba + n_attn * attention + (n_mamba + n_attn) * mlp


def head_weights(model):
    return int(model['hidden_size']) * int(model['published_vocab_size'])


def attention_ops_per_key(model):
    """Operations ONE query spends on ONE key in ONE attention layer,
    over all its heads: 2 d for q.k and 2 d for the weighted value."""
    return 4 * int(model['num_heads']) * int(model['head_dim'])


def window_ops(model, *, prefill_tokens, decoded_tokens, positions):
    """Operations of a window of serving: two a weight for every true
    prompt token and every decoded token, the head once a delivered
    token, the state's update and read-out a token and Mamba layer
    (the recurrence's count, for prefill and decode alike), and the
    attention layers' keys (`positions`: the keys every query saw,
    summed, prefill and decode)."""
    n_mamba, n_attn = layers_of(model)
    tokens = int(prefill_tokens) + int(decoded_tokens)
    keys = positions['prefill_full'] + positions['decode_full']
    return (2 * token_weights(model) * tokens
            + 2 * head_weights(model) * int(decoded_tokens)
            + n_mamba * ssm_decode_update(model)[0] * tokens
            + n_attn * attention_ops_per_key(model) * keys)


def kv_block_bytes(model, block_size):
    """Bytes of ONE block of ONE attention layer: keys and values."""
    return 2 * int(block_size) * int(model['num_kv_heads']) \
        * int(model['head_dim']) * POOL_BYTES


def paged_read(model, block_size, blocks):
    """(operations, bytes) of the paged decode kernel reading `blocks`
    blocks of an attention LAYER (summed over token steps and rows) in
    every attention layer."""
    _, n_attn = layers_of(model)
    ops = attention_ops_per_key(model) * n_attn * int(blocks) \
        * int(block_size)
    return ops, n_attn * int(blocks) * kv_block_bytes(model, block_size)
