"""Operations and bytes the routed decoder with a shared expert, gated
attention and leading dense layers needs (`paddle_tpu/models/afmoe.py`),
from shapes and counts alone, for the `.moe_shared` metrics' rooflines
and the whole window's share of the peak.  Only what the algorithm
needs counts: the experts a token was routed to and the shared one,
the dense MLP in the dense layers only, true prompt tokens and never a
bucket's padding, the keys a query's band holds and never a tile's.
What the program does beyond that counts against it.  The counts that
do not depend on what stands where the MLP would (one expert's
weights, the head, attention's operations a key, the pools' bytes, the
layers by kind of attention) are `smallthinker_flops.py`'s."""
from benchmark.flops import least_seconds, peaks  # noqa: F401
from benchmark.smallthinker_flops import (  # noqa: F401
    WEIGHT_BYTES, attention_ops_per_key, expert_weights, head_weights,
    layers_of)


def routed_layers(model):
    """Layers that route: those behind the leading dense ones."""
    return int(model['num_layers']) - int(model['num_dense_layers'])


def attention_weights(model):
    """q, k, v and output projections and the output gate's, ONE
    layer."""
    h, d = int(model['hidden_size']), int(model['head_dim'])
    hq, hkv = int(model['num_heads']), int(model['num_kv_heads'])
    return 3 * h * hq * d + 2 * h * hkv * d


def routed_token_weights(model):
    """Weights of every matrix ONE token meets in ONE routed layer:
    attention with its gate, the router, the experts it is routed to
    and the shared ones (active weights only)."""
    return (attention_weights(model)
            + int(model['hidden_size']) * int(model['num_experts'])
            + (int(model['experts_per_token'])
               + int(model['num_shared_experts'])) * expert_weights(model))


def dense_token_weights(model):
    """The same in ONE leading dense layer: attention with its gate
    and the MLP."""
    return attention_weights(model) + 3 * int(model['hidden_size']) \
        * int(model['dense_intermediate_size'])


def token_weights(model):
    """Over all the layers that are run."""
    return (int(model['num_dense_layers']) * dense_token_weights(model)
            + routed_layers(model) * routed_token_weights(model))


def window_ops(model, *, prefill_tokens, decoded_tokens, positions):
    """Operations of a window of serving: two a weight for every true
    prompt token prefilled and every token decoded, the head once a
    delivered token (a prefill computes it at its last position only),
    and attention at each query's own band: `positions` holds the keys
    seen, summed over the queries, by one full and by one window
    layer, for prefill and decode."""
    full, window = layers_of(model)
    keys = (full * (positions['prefill_full'] + positions['decode_full'])
            + window * (positions['prefill_window']
                        + positions['decode_window']))
    return (2 * token_weights(model)
            * (int(prefill_tokens) + int(decoded_tokens))
            + 2 * head_weights(model) * int(decoded_tokens)
            + attention_ops_per_key(model) * keys)


def experts_prefill(model, true_tokens, prefills):
    """(operations, bytes) of the ROUTED experts' product of
    `true_tokens` prompt tokens over `prefills` dispatches, in every
    routed layer: two a weight of each token's chosen experts; every
    expert's weights read once a dispatch.  (The shared expert runs
    under a scope of its own.)"""
    ops = 2 * int(true_tokens) * int(model['experts_per_token']) \
        * expert_weights(model)
    moved = int(model['num_experts']) * expert_weights(model) \
        * WEIGHT_BYTES * int(prefills)
    return ops * routed_layers(model), moved * routed_layers(model)
