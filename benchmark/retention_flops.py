"""Operations and bytes that power retention of degree 2 needs, from
shapes alone, for the retention metrics' rooflines.  `D` is the
symmetric square's size, d (d + 1) / 2, whatever layout or padding the
program holds its state in: what the program moves beyond that counts
against it.  State and operands are float32 (the configuration's
`state.dtype`).  Kept with the benchmark, beside flops.py."""
from benchmark.flops import least_seconds, peaks  # noqa: F401

STATE_BYTES = 4


def features(head_dim):
    return head_dim * (head_dim + 1) // 2


def decode_update(model):
    """(operations, bytes) of ONE live row's update in ONE layer: per
    key/value head the state S [D, d] and z [D] are read and written
    once; q, k, v and the gate are read and y is written; the update
    is a scale and a rank-one add (3 operations an element of S and of
    z), phi(k) and phi(q) two multiplies a feature, the read-out
    2 D (d + 1) a query head."""
    d = int(model['head_dim'])
    hq, hkv = int(model['num_heads']), int(model['num_kv_heads'])
    D = features(d)
    moved = STATE_BYTES * (2 * hkv * D * (d + 1)      # S, z in and out
                           + 2 * hq * d               # q in, y out
                           + 2 * hkv * d + hkv)       # k, v, gate
    ops = (3 * hkv * D * (d + 1) + 2 * D * (hkv + hq)
           + 2 * hq * D * (d + 1))
    return ops, moved


def prefill_call(model, length):
    """(operations, bytes) of ONE row of `length` positions in ONE
    layer, from the empty state: the masked square (half of 4 T^2 d a
    query head, as flash_fwd_call counts a causal square), the state
    at the end (2 T D (d + 1) a key/value head) and the feature map;
    q, k, v, gate read, y and the state written once."""
    d = int(model['head_dim'])
    hq, hkv = int(model['num_heads']), int(model['num_kv_heads'])
    D = features(d)
    T = int(length)
    ops = (4 * hq * T * T * d // 2 + 2 * hkv * T * D * (d + 1)
           + 2 * hkv * T * D)
    moved = STATE_BYTES * (2 * hq * T * d + 2 * hkv * T * d + hkv * T
                           + hkv * D * (d + 1))
    return ops, moved


def decode_step_ops_per_token(model):
    """Operations ONE served token needs in a decode step of the whole
    decoder: two a weight of every matrix a token meets (q, k, v, gate
    and output projections, the gated MLP's three, the untied head over
    the published vocabulary; the embedding is a lookup) and the state's
    update and read-out in every layer."""
    h, d = int(model['hidden_size']), int(model['head_dim'])
    hq, hkv = int(model['num_heads']), int(model['num_kv_heads'])
    layer = (2 * h * hq * d + 2 * h * hkv * d + h * hkv
             + 3 * h * int(model['intermediate_size']))
    weights = int(model['num_layers']) * layer \
        + h * int(model['published_vocab_size'])
    return 2 * weights + int(model['num_layers']) * decode_update(model)[0]
