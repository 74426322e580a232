"""Mamba-2 state-space mixing (the SSD layer of arXiv:2405.21060) for the
hybrid decoders: the causal depthwise convolution in front of it, the
chunked scan of a prompt and the one-token state update.

Per head `h` of `head_dim` P, with a state of `d_state` N shared
projections B and C (one group), a step size `dt` (already through its
softplus) and a decay rate `A_h < 0`:

    S_t,h = exp(dt_t,h A_h) S_{t-1},h + dt_t,h x_t,h B_t^T      S_h [P, N]
    y_t,h = S_t,h C_t

(the skip `D x` and the gate are the model's).  Everything here is
float32: the state is an accumulator over the whole sequence.

**The state's layout** is `S [slots, N, H * P]`: the state dimension on
the sublanes and every head's `head_dim` side by side on the lanes, so
that a row's state is ONE [N, H P] matrix (128 x 4096 at Granite-4.0-H's
widths).  The update is then a lane row of decays times it plus a column
of B times a lane row of `dt x`, and the read-out a column of C times it
summed over the sublanes; no head is padded, whatever `head_dim` is.
`heads_of` turns a slot into the mathematical `[H, P, N]`.

Three entry points, each under its own name scope:

- `causal_conv1d` (`ssm.conv`): `silu(conv(x) + b)` over right-padded
  rows, and each row's conv state, its last `K - 1` TRUE inputs (zeros
  before position 0); `conv_step` is one token of it against that state.
- `ssd_prefill` (`ssm.prefill`): the chunked form over right-padded
  rows, from the empty state.  Within a chunk the masked quadratic form,
  plus what the state at the chunk's start adds; the state at its end
  goes on to the next chunk.  The caller hands `dt` as 0 at a pad: a pad
  then neither decays the state nor feeds it, and the state returned is
  the one at the row's true length.
- `ssm_decode` (`ssm.decode`): one token a live row, the row's slot of
  the state rewritten.  Two paths, one signature; `can_use_pallas`
  chooses by what it can see (a TPU or interpret mode, no mesh,
  shapes).  The Pallas kernel `ssm_decode` streams each row's slot
  through VMEM once and writes it back in place (`input_output_aliases`:
  the compiled decode module holds the state once); the plain version is
  gather, update, scatter in `jax.numpy` (the CPU, a mesh, other shapes
  and dtypes, and the oracle of the tests).  A row that is not active
  leaves its slot as it was.
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _gating

__all__ = ['causal_conv1d', 'conv_step', 'ssd_prefill', 'ssm_decode',
           'can_use_pallas', 'heads_of', 'state_layout', 'PREFILL_CHUNK']

F32 = jnp.float32
# the scan's products accumulate the state; on a TPU a float32 matmul at
# the default precision rounds its operands to bfloat16
PRECISION = jax.lax.Precision.HIGHEST
# Positions a prefill chunk holds: the published `mamba_chunk_size`
PREFILL_CHUNK = 256
# Lanes of a row's state a grid step of the kernel holds: [N, 2048]
# float32 is 1 MB, in and out and double-buffered 4 MB; a row is two
# steps at 64 heads of 64
_TILE = 2048
# lanes the kernel's body works on at a time
_PIECE = 512


# -- the convolution ----------------------------------------------------------
def causal_conv1d(x, weight, bias, lengths):
    """x [B, T, C] right-padded, weight [K, C] (tap K - 1 multiplies the
    position itself), bias [C], lengths [B] (>= 1).  Returns
    silu(conv(x) + bias) [B, T, C] float32 (a pad position's output
    means nothing) and the conv state [B, K - 1, C] float32: each row's
    inputs at its last K - 1 true positions, zeros before position 0."""
    with jax.named_scope('ssm.conv'):
        K = weight.shape[0]
        B, T, C = x.shape
        x = x.astype(F32)
        w = weight.astype(F32)
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
        y = sum(w[k] * xp[:, k:k + T] for k in range(K))
        y = jax.nn.silu(y + bias.astype(F32))
        # input position t sits at t + K - 1 of xp; the last K - 1 true
        # ones are positions len - K + 1 .. len - 1
        at = lengths.astype(jnp.int32)[:, None] + jnp.arange(K - 1)[None]
        state = jnp.take_along_axis(xp, at[..., None], axis=1)
        return y, state


def conv_step(x, state, weight, bias):
    """One token a row: x [R, C], state [R, K - 1, C] (the last inputs,
    oldest first).  Returns silu(conv + bias) [R, C] and the new state."""
    with jax.named_scope('ssm.conv'):
        window = jnp.concatenate([state.astype(F32),
                                  x.astype(F32)[:, None]], axis=1)
        w = weight.astype(F32)
        # taps multiplied and added on the VPU, as the prefill's are: a
        # dot would round the window to bfloat16 on a TPU
        y = sum(w[k] * window[:, k] for k in range(w.shape[0]))
        return jax.nn.silu(y + bias.astype(F32)), window[:, 1:]


# -- the state's layout ---------------------------------------------------------
def state_layout(heads, head_dim, d_state):
    """The shape of one slot of the state: [N, H * P]."""
    return (int(d_state), int(heads) * int(head_dim))


def heads_of(S, heads):
    """[..., N, H * P] -> the mathematical [..., H, P, N]."""
    *lead, n, hp = S.shape
    x = S.reshape(*lead, n, heads, hp // heads)
    return jnp.moveaxis(x, -3, -1)


def _to_layout(S):
    """[..., H, P, N] -> [..., N, H * P]."""
    *lead, h, p, n = S.shape
    return jnp.moveaxis(S, -1, -3).reshape(*lead, n, h * p)


# -- prefill: the chunked form ------------------------------------------------------
def _chunk(S, xs):
    """One chunk of every row: S [B, H, P, N] at its start (None: the
    empty state), x [B, Q, H, P], dt [B, Q, H], B_ and C [B, Q, N], and
    the log decays a [B, Q, H].  Returns y [B, Q, H, P] and the state at
    the chunk's end."""
    x, dt, Bm, Cm, a = xs
    Q = x.shape[1]
    # a decay is the difference of two running sums of the chunk's log
    # decays: at a fast head those reach thousands and float32 keeps
    # some 5e-5 of a recent position's weight; summing each decay over
    # the positions it spans keeps 1e-7 and made a prefill dispatch 45%
    # slower on a TPU v5e (PERF.md section 6)
    acum = jnp.cumsum(a, axis=1)                          # [B, Q, H]
    t = jnp.arange(Q)
    seen = t[None, :] <= t[:, None]                       # [Q(t), Q(s)]
    gap = acum[:, :, None, :] - acum[:, None, :, :]       # [B, t, s, H]
    decay = jnp.where(seen[None, :, :, None],
                      jnp.exp(jnp.where(seen[None, :, :, None], gap, 0.0)),
                      0.0)
    cb = jnp.einsum('btn,bsn->bts', Cm, Bm, precision=PRECISION)
    u = x * dt[..., None]                                 # [B, Q, H, P]
    y = jnp.einsum('bts,btsh,bshp->bthp', cb, decay, u,
                   precision=PRECISION)
    end = acum[:, -1]                                     # [B, H]
    feed = u * jnp.exp(end[:, None] - acum)[..., None]    # [B, Q, H, P]
    S_new = jnp.einsum('bshp,bsn->bhpn', feed, Bm, precision=PRECISION)
    if S is not None:
        y = y + jnp.einsum('btn,bhpn->bthp', Cm, S, precision=PRECISION) \
            * jnp.exp(acum)[..., None]
        S_new = S_new + jnp.exp(end)[..., None, None] * S
    return y, S_new


def ssd_prefill(x, dt, A, B, C, *, chunk=None):
    """The chunked scan over right-padded rows, from the empty state.

    x [B, T, H, P], dt [B, T, H] (softplus applied, 0 at every pad), A
    [H] (< 0), B and C [B, T, N].  Returns y [B, T, H, P] float32 (a
    pad's means nothing) and each row's state at its end, in the cache's
    layout [B, N, H * P]; `chunk` positions a chunk, PREFILL_CHUNK unless
    a test asks for less."""
    with jax.named_scope('ssm.prefill'):
        Bt, T, H, P = x.shape
        Q = min(int(chunk or PREFILL_CHUNK), T)
        pad = -T % Q
        n = (T + pad) // Q

        def chunks(v):
            v = jnp.pad(v.astype(F32),
                        [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
            return jnp.moveaxis(v.reshape(Bt, n, Q, *v.shape[2:]), 1, 0)

        dt = dt.astype(F32)
        xs = tuple(chunks(v) for v in (x, dt, B, C, dt * A.astype(F32)))
        y, S = _chunk(None, tuple(v[0] for v in xs))
        ys = [y[None]]
        if n > 1:
            def body(S, v):
                y, S = _chunk(S, v)
                return S, y

            S, more = jax.lax.scan(body, S, tuple(v[1:] for v in xs))
            ys.append(more)
        y = jnp.moveaxis(jnp.concatenate(ys, axis=0), 0, 1)
        return y.reshape(Bt, n * Q, H, P)[:, :T], _to_layout(S)


# -- decode: one token a live row -----------------------------------------------------
def _tile(width):
    """Lanes a grid step holds: a divisor of the row's H * P that is a
    whole number of pieces; None where there is none."""
    for tile in (_TILE, _PIECE):
        if width % tile == 0:
            return tile
    return None


def can_use_pallas(S):
    """True iff `ssm_decode` takes the Pallas kernel for this state: a
    TPU (or interpret mode) and no mesh, float32, N whole sublane tiles
    and H * P a whole number of the kernel's pieces."""
    return (_gating.pallas_backend_ok() and S.dtype == jnp.float32
            and S.ndim == 3 and S.shape[1] % 8 == 0
            and _tile(S.shape[2]) is not None)


def _decode_kernel(slots_ref, dec_ref, u_ref, b_ref, c_ref, s_ref, y_ref,
                   s_out_ref):
    """One grid step = one (row, lane tile): the tile of the row's state
    [N, tile] is scaled by the heads' decays, takes B (a column) times
    dt x (a lane row), goes back where it came from, and is read by C
    on the way, a piece of lanes at a time."""
    del slots_ref                       # the index maps read it
    b = b_ref[0]                        # [N, 1]
    c = c_ref[0]
    for lo in range(0, s_ref.shape[-1], _PIECE):
        lanes = slice(lo, lo + _PIECE)
        s_new = dec_ref[0, :, lanes] * s_ref[0, :, lanes] \
            + b * u_ref[0, :, lanes]    # [1,piece] x [N,piece] + [N,1] x [1,piece]
        s_out_ref[0, :, lanes] = s_new
        y_ref[0, :, lanes] = jnp.sum(s_new * c, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=('tile', 'interpret'))
def _ssm_decode(slots, dec, u, b, c, S, *, tile, interpret=False):
    """dec, u [R, 1, H P], b, c [R, N, 1], S [slots, N, H P], slots [R]
    int32 (distinct).  Returns y [R, 1, H P] and S with the rows' slots
    rewritten."""
    R, _, width = dec.shape
    n = S.shape[1]

    def lanes(r, t, slots):
        return (r, 0, t)

    def col(r, t, slots):
        return (r, 0, 0)

    def slot(r, t, slots):
        return (slots[r], 0, t)

    y, S = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, width // tile),
            in_specs=[pl.BlockSpec((1, 1, tile), lanes),
                      pl.BlockSpec((1, 1, tile), lanes),
                      pl.BlockSpec((1, n, 1), col),
                      pl.BlockSpec((1, n, 1), col),
                      pl.BlockSpec((1, n, tile), slot)],
            out_specs=[pl.BlockSpec((1, 1, tile), lanes),
                       pl.BlockSpec((1, n, tile), slot)]),
        out_shape=[jax.ShapeDtypeStruct((R, 1, width), F32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        # operand 5 (after the prefetched slots) is S; output 1 is S
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary', 'arbitrary')),
        interpret=interpret,
        name='ssm_decode',
    )(slots.astype(jnp.int32), dec, u, b, c, S)
    return y, S


def _decode_plain(slots, dec, u, b, c, S):
    """Gather the rows' slots, update, read, scatter back."""
    rows = dec * S[slots].astype(F32) + b * u             # [R, N, H P]
    y = jnp.sum(rows * c, axis=1, keepdims=True)
    return y, S.at[slots].set(rows.astype(S.dtype))


def ssm_decode(x, dt, A, B, C, S, slots, active):
    """One token a row.

    x [R, H, P], dt [R, H] (softplus applied), A [H] (< 0), B and C
    [R, N], S [slots, N, H P] (float32 for the kernel; another dtype
    takes the plain path, which computes in float32 and stores in S's),
    slots [R] int (DISTINCT: a row that is padding names a slot no live
    row holds), active [R] bool.  Returns y [R, H, P] float32 and S.  A
    row that is not active leaves its slot as it was, and its y means
    nothing."""
    with jax.named_scope('ssm.decode'):
        R, H, P = x.shape
        live = active[:, None]
        dt = jnp.where(live, dt.astype(F32), 0.0)           # [R, H]
        dec = jnp.repeat(jnp.exp(dt * A.astype(F32)), P, axis=1)
        u = (x.astype(F32) * dt[..., None]).reshape(R, 1, H * P)
        dec = dec.reshape(R, 1, H * P)
        b = B.astype(F32)[..., None]                         # [R, N, 1]
        c = C.astype(F32)[..., None]
        if can_use_pallas(S):
            y, S = _ssm_decode(slots, dec, u, b, c, S,
                               tile=_tile(S.shape[2]),
                               interpret=_gating.INTERPRET)
        else:
            y, S = _decode_plain(slots, dec, u, b, c, S)
        return y.reshape(R, H, P), S
