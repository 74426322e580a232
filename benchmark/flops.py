"""Operations and bytes the algorithm needs, from shapes alone, and the
chip's peaks.  Kept with the benchmark so that no PR that claims a gain
can change the yardstick."""
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind):
    """The published peaks of this device; an unknown device is an
    error, never a default."""
    with open(os.path.join(_HERE, 'peaks.json')) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f'no peaks for device kind {device_kind!r} in '
                       f'benchmark/peaks.json (has {sorted(table)})')
    return table[device_kind]


def train_flops_per_token(model, seq_len):
    """Forward + backward operations one trained token requires (the
    backward is twice the forward; recomputation does not count).

    Per layer and token the matrix multiplications read 12 h^2 weights
    (qkv 3, proj 1, MLP 4 + 4): 24 h^2 operations.  Causal attention
    meets seq_len / 2 keys on average, 2 h operations a key each for
    QK^T and for AV: 2 h seq_len.  The tied head is 2 h V over the
    published vocabulary (padding rows are not model work).
    """
    h = int(model['hidden_size'])
    layers = int(model['num_layers'])
    vocab = int(model['published_vocab_size'])
    fwd = layers * (24 * h * h + 2 * h * int(seq_len)) + 2 * h * vocab
    return 3 * fwd


def flash_fwd_call(model, batch, seq_len, bytes_per_el=2):
    """(operations, bytes) of ONE causal flash-attention forward over
    [batch * heads, seq_len, head_dim]: half of the 4 T^2 d a full
    square needs, and q, k, v read and o written once."""
    h = int(model['hidden_size'])
    heads = int(model['num_heads'])
    hd = h // heads
    bh = int(batch) * heads
    ops = 4 * bh * int(seq_len) ** 2 * hd // 2
    moved = 4 * bh * int(seq_len) * hd * bytes_per_el
    return ops, moved


def least_seconds(ops, moved, peak):
    """The roofline's least time and which roof sets it."""
    t_ops = ops / peak['bf16_flops_per_s']
    t_mem = moved / peak['hbm_bytes_per_s']
    return (t_ops, 'compute') if t_ops >= t_mem else (t_mem, 'memory')
