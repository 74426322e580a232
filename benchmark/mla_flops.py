"""Operations and bytes the latent-attention decoder with held experts
needs (`paddle_tpu/models/joyai.py`), from shapes and the program's
counts alone, for the `.mla` metrics' rooflines and the window's share
of the peak.  Only what the algorithm needs counts: a position's 576
numbers of latent and rotary key (never the 64 lanes a pool row is
padded with), a causal prefill's (query, key) pairs of true prompt
positions (never a bucket's padding or a tile's), the held experts a
token was routed to, the dense MLP in the dense layer only.  What the
program does beyond that counts against it."""
from benchmark.flops import least_seconds, peaks  # noqa: F401

WEIGHT_BYTES = 2        # weights_dtype, bfloat16


def routed_layers(model):
    return int(model['num_layers']) - int(model['num_dense_layers'])


def expert_weights(model):
    """Weights of ONE expert: gate, up and down."""
    return 3 * int(model['hidden_size']) * int(model['intermediate_size'])


def attention_weights(model):
    """The latent attention's matrices of ONE layer: q's two low-rank
    halves, the latent and rotary key's projection, the latent up to
    every head's keys and values, the output."""
    h, H = int(model['hidden_size']), int(model['num_heads'])
    nope, rope = int(model['qk_nope_head_dim']), int(model['qk_rope_head_dim'])
    r, dv = int(model['kv_lora_rank']), int(model['v_head_dim'])
    return (h * int(model['q_lora_rank'])
            + int(model['q_lora_rank']) * H * (nope + rope)
            + h * (r + rope) + r * H * (nope + dv) + H * dv * h)


def token_weights(model):
    """Weights of the matrices ONE token meets in every layer run, the
    routed experts left out (they are counted by assignment): attention,
    the dense MLP in the dense layers, the router and the shared expert
    in the routed ones."""
    h = int(model['hidden_size'])
    dense = int(model['num_dense_layers']) * 3 * h \
        * int(model['dense_intermediate_size'])
    routed = routed_layers(model) * (
        h * int(model['num_experts'])
        + int(model['num_shared_experts']) * expert_weights(model))
    return int(model['num_layers']) * attention_weights(model) + dense \
        + routed


def head_weights(model):
    return int(model['hidden_size']) * int(model['published_vocab_size'])


def position_bytes(model, itemsize=4):
    """Bytes ONE position keeps in ONE layer: latent and rotary key."""
    return (int(model['kv_lora_rank']) + int(model['qk_rope_head_dim'])) \
        * itemsize


def latent_read(model, block_size, kv_blocks, itemsize=4):
    """(operations, bytes) of the decode kernel reading `kv_blocks`
    blocks of ONE layer (a dispatch's token steps summed) in every
    layer: the bytes a block's positions need; the operations are not
    counted (the roof is the bytes)."""
    return 0, int(kv_blocks) * int(block_size) \
        * position_bytes(model, itemsize) * int(model['num_layers'])


def prefill_flash(model, attn_pairs):
    """(operations, bytes) of the expanded prefill's flash forward over
    `attn_pairs` causal (query, key) pairs of true positions in every
    layer: 2 (nope + rope) for the score and 2 v for the weighted value,
    every head; q, k, v read once are not counted (the roof is
    compute)."""
    dk = int(model['qk_nope_head_dim']) + int(model['qk_rope_head_dim'])
    return (int(attn_pairs) * int(model['num_heads']) * 2
            * (dk + int(model['v_head_dim'])) * int(model['num_layers']), 0)


def experts_stream(model, experts_hit):
    """(operations, bytes) of reading `experts_hit` held experts'
    weights once each, summed over token steps and routed layers."""
    return 0, int(experts_hit) * expert_weights(model) * WEIGHT_BYTES


def window_ops(model, *, prefill_tokens, decoded_tokens, decode_assignments,
               prefill_pairs, decode_keys):
    """Operations of a window of serving: two a weight of every matrix a
    true prompt token or a decoded token meets; of the routed experts,
    the held ones each decoded token was routed to (the program's count,
    `decode_assignments`) and, for prompt tokens, the held share of
    their eight (experts_per_token x held / num_experts a routed layer);
    the head once a delivered token; attention as each path computes
    it: a prefill's pairs expanded (2 (nope + rope + v) a pair and head)
    and a decoded token's keys absorbed (2 (latent + rope) + 2 latent a
    key and head, `decode_keys` the keys seen summed over the tokens),
    in every layer."""
    H, L = int(model['num_heads']), int(model['num_layers'])
    r, rope = int(model['kv_lora_rank']), int(model['qk_rope_head_dim'])
    prompt_experts = int(prefill_tokens) * routed_layers(model) \
        * int(model['experts_per_token']) * int(model['held_experts'][1]) \
        / int(model['num_experts'])
    weights = token_weights(model) * (int(prefill_tokens)
                                      + int(decoded_tokens)) \
        + expert_weights(model) * (int(decode_assignments) + prompt_experts)
    attention = prefill_flash(model, prefill_pairs)[0] \
        + int(decode_keys) * H * 2 * (2 * r + rope) * L
    return 2 * weights + 2 * head_weights(model) * int(decoded_tokens) \
        + attention
