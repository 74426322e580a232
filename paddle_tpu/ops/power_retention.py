"""Power retention, degree 2: the linear-cost layer of the retention
decoders (power attention / power retention, arXiv:2507.04239).

Per key/value head, with `phi` the symmetric square of a head vector,
so that `phi(q) . phi(k) = (q . k)^2`, a log decay `g <= 0` a token
and `s = 1/sqrt(d)`:

    S_t = exp(g_t) S_{t-1} + phi(k_t) v_t^T      z_t = exp(g_t) z_{t-1} + phi(k_t)
    y_t = phi(s q_t)^T S_t / (phi(s q_t)^T z_t + eps)

Every query head of a group reads its key/value head's state.  Two
entry points, both float32 inside:

- `retention_prefill`: the chunked form over a padded prompt.  Within a
  chunk the masked quadratic form, plus what the state at the chunk's
  start adds; the state at the chunk's end goes on to the next.  A
  position at or past a row's true length adds neither decay nor
  `phi(k) v^T`, so the state returned is the one at the true length.
- `retention_decode`: one token a live row: scale, rank-one update,
  read-out, the row's slot of the state rewritten.  Rows that are not
  active leave their slot as it was.

**The feature map's layout.**  `(q . k)^2 = sum_o sum_a q_a q_{a+o} k_a
k_{a+o}` over the circular offsets `o` of the head dimension `d`;
offsets `o` and `d - o` give the same products.  So `phi(x)[o, a] =
c_o x_a x_{(a+o) mod d}` for `o = 0 .. d/2`, with `c_0 = 1`, `c_o =
sqrt(2)` in between and `c_{d/2} = 1` (that row holds every pair
twice).  That is `d/2 + 1` rows of `d` lanes: 8,320 features for `d`
128 where the symmetric square has 8,256, the 64 more being the second
copies in the last row.  Stated because it is the state's size: the
pad buys rows of whole 128-lane vregs, each one lane rotation of the
head vector.  `phi(q) . phi(k)` is `(q . k)^2` exactly.

**The state's layout** is `S [slots, kv heads, d, D]`, the value
dimension outside the features: a rank-one update is then a column of
`v` times a row of `phi(k)`, and the read-out contracts lanes with
lanes.  `z [slots, kv heads, D]`.

Two paths for the decode update, one signature; `can_use_pallas`
chooses by what it can see (a TPU or interpret mode, no mesh, shapes):
the Pallas kernel `retention_decode` streams each live row's `S`
through VMEM once and writes it back in place (`input_output_aliases`:
the compiled decode module holds the state once), the plain version is
gather, update, scatter in `jax.numpy` (the CPU, a mesh, other shapes,
and the oracle of the tests).  Prefill is `jax.numpy` everywhere: at
the serving cell's prompts it is a few matmuls XLA does well.
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import _gating

__all__ = ['feature_rows', 'num_features', 'phi', 'retention_prefill',
           'retention_decode', 'can_use_pallas', 'PREFILL_CHUNK',
           'EPS_R']

F32 = jnp.float32
EPS_R = 1e-6
# Positions a prefill chunk holds.  A chunk costs its tokens 4 C d
# operations a query head inside it (the masked square) against
# 2 D (d + 1) for reading the state: splitting a prompt pays only past
# C = D (d + 1) / (2 d) ~ 4,200 positions at d 128, and the square's
# [C, C] scores bound C from above (1,024: 168 MB a layer at 40 query
# heads).  The serving cell's prompts are one chunk each.
PREFILL_CHUNK = 1024
# The state is a float32 accumulator; on a TPU a float32 matmul at the
# default precision rounds its operands to bfloat16.
PRECISION = jax.lax.Precision.HIGHEST


def feature_rows(head_dim):
    return head_dim // 2 + 1


def num_features(head_dim):
    """D as the state holds it: (d/2 + 1) d, see the module's head."""
    if head_dim % 2:
        raise ValueError(f'head_dim {head_dim} is odd')
    return feature_rows(head_dim) * head_dim


def phi(x):
    """[..., d] -> [..., D] float32, the layout of the module's head."""
    x = x.astype(F32)
    d = x.shape[-1]
    rows = feature_rows(d)
    coef = jnp.full((rows,), math.sqrt(2.0), F32)
    coef = coef.at[0].set(1.0).at[rows - 1].set(1.0)
    rolled = jnp.stack([jnp.roll(x, -o, axis=-1) for o in range(rows)],
                       axis=-2)                       # [..., rows, d]
    out = coef[:, None] * x[..., None, :] * rolled
    return out.reshape(*x.shape[:-1], rows * d)


def _grouped(q, num_kv):
    """[..., Hq, d] -> [..., Hkv, G, d]: query head i reads key/value
    head i // G."""
    *lead, hq, d = q.shape
    return q.reshape(*lead, num_kv, hq // num_kv, d)


# -- prefill: the chunked form -------------------------------------------------
def _chunk_head(q, k, v, g, valid, state):
    """One chunk of one (row, key/value head).  q [C,G,d] (already
    scaled), k, v [C,d], g [C] (0 where not valid), valid [C], state
    (S [d,D], z [D]) or None for the empty state.  Returns y [C,G,d]
    and the state at the chunk's end."""
    C = q.shape[0]
    b = jnp.cumsum(g)                                  # [C]
    t = jnp.arange(C)
    seen = (t[None, :] <= t[:, None]) & valid[None, :]
    w = jnp.where(seen, jnp.exp(jnp.where(
        seen, b[:, None] - b[None, :], 0.0)), 0.0)     # [C(t),C(l)]
    sc = jnp.einsum('tgd,ld->gtl', q, k, precision=PRECISION)
    a = w[None] * sc * sc                              # [G,C,C]
    num = jnp.einsum('gtl,lc->tgc', a, v, precision=PRECISION)
    den = a.sum(-1).T                                  # [C,G]
    if state is not None:
        S, z = state
        pq = phi(q) * jnp.exp(b)[:, None, None]        # [C,G,D]
        num = num + jnp.einsum('tgD,cD->tgc', pq, S, precision=PRECISION)
        den = den + jnp.einsum('tgD,D->tg', pq, z, precision=PRECISION)
    y = num / (den[..., None] + EPS_R)
    # the state at the chunk's end; b stands still past a row's length
    wk = jnp.where(valid, jnp.exp(b[-1] - b), 0.0)
    pk = phi(k) * wk[:, None]                          # [C,D]
    S_new = jnp.einsum('lc,lD->cD', v, pk, precision=PRECISION)
    z_new = pk.sum(0)
    if state is not None:
        S_new = S_new + jnp.exp(b[-1]) * S
        z_new = z_new + jnp.exp(b[-1]) * z
    return y, (S_new, z_new)


def _chunk(xs, state):
    """One chunk of every (row, key/value head), one after another:
    q [N,C,G,d], k, v [N,C,d], g, valid [N,C] with N = rows x heads.
    The heads share nothing, and one at a time keeps the temporaries
    (the [G,C,C] square, phi(k) [C,D]) an eighth of a layer's."""
    if state is None:
        return jax.lax.map(lambda x: _chunk_head(*x, None), xs)
    return jax.lax.map(lambda x: _chunk_head(*x[:-1], x[-1]),
                       (*xs, state))


def retention_prefill(q, k, v, g, lengths, *, chunk=None):
    """The chunked form over right-padded rows, from the empty state.

    q [B,T,Hq,d], k, v [B,T,Hkv,d], g [B,T,Hkv] (log decay, <= 0),
    lengths [B] (true lengths, >= 1); `chunk` positions a chunk,
    PREFILL_CHUNK unless a test asks for less.  Returns y [B,T,Hq,d]
    float32 (rows past a length are finite and mean nothing) and the
    state (S [B,Hkv,d,D], z [B,Hkv,D]) at each row's TRUE length.
    """
    with jax.named_scope('retention.prefill'):
        B, T, hq, d = q.shape
        hkv = k.shape[2]
        G = hq // hkv
        C = min(int(chunk or PREFILL_CHUNK), T)
        pad = -T % C
        n = (T + pad) // C
        valid = jnp.arange(T + pad)[None, :] < lengths[:, None]
        g = jnp.where(valid[:, :T, None], g.astype(F32), 0.0)
        qs = _grouped(q.astype(F32) * (1.0 / math.sqrt(d)), hkv)

        def heads_out(x):
            """[B,T,Hkv,...] -> [n, B*Hkv, C, ...]: chunks outside,
            one entry a (row, head)."""
            x = jnp.pad(x.astype(F32), [(0, 0), (0, pad)] + [(0, 0)] * (
                x.ndim - 2))
            x = jnp.moveaxis(x, 2, 1).reshape(
                B * hkv, n, C, *x.shape[3:])
            return jnp.moveaxis(x, 1, 0)

        xs = tuple(heads_out(x) for x in (qs, k, v, g)) + (
            jnp.repeat(valid, hkv, axis=0).reshape(B * hkv, n, C)
            .swapaxes(0, 1),)
        y, state = _chunk(tuple(x[0] for x in xs), None)
        ys = [y[None]]
        if n > 1:
            def body(st, x):
                y, st = _chunk(x, st)
                return st, y

            state, more = jax.lax.scan(
                body, state, tuple(x[1:] for x in xs))
            ys.append(more)
        y = jnp.concatenate(ys, axis=0)                # [n,B*Hkv,C,G,d]
        y = jnp.moveaxis(y, 0, 1).reshape(B, hkv, n * C, G, d)[:, :, :T]
        y = jnp.moveaxis(y, 1, 2).reshape(B, T, hq, d)
        S, z = state
        return y, (S.reshape(B, hkv, *S.shape[1:]),
                   z.reshape(B, hkv, *z.shape[1:]))


# -- decode: one token a live row ---------------------------------------------
# Features a grid step of the kernel holds: the S tile is [d, tile]
# float32, in and out and double-buffered (4 x 128 x 1664 x 4 B = 3.4
# MB at d 128), inside the 16 MB scoped VMEM default.
_TILE_ROWS = 13


def _tile(head_dim):
    """Features a grid step holds, a whole number of feature rows that
    divides D; None where no such tile exists."""
    rows = feature_rows(head_dim)
    for r in range(min(_TILE_ROWS, rows), 0, -1):
        if rows % r == 0:
            return r * head_dim
    return None


def _vmem_bytes(d, g8, tile):
    """What a grid step holds: the S tile in and out and phi(q),
    double-buffered, the updated tile, and room for the rest; None
    (the compiler's 16 MB default) where that is enough."""
    need = (5 * d + 4 * g8) * tile * 4 + (4 << 20)
    return need if need > (16 << 20) else None


def can_use_pallas(S, q):
    """True iff `retention_decode` takes the Pallas kernel for these
    operands: a TPU (or interpret mode) and no mesh, a float32 state
    whose head dimension is a whole number of 128-lane vregs."""
    d = q.shape[-1]
    return (_gating.pallas_backend_ok() and S.dtype == jnp.float32
            and d % 128 == 0 and S.shape[-1] == num_features(d)
            and _tile(d) is not None)


def _decode_kernel(slots_ref, pq_ref, pk_ref, v_ref, dec_ref, s_ref,
                   num_ref, s_out_ref):
    """One grid step = one (row, key/value head, feature tile): the
    tile of S is scaled, takes its rank-one update and goes back where
    it came from; the group's query heads read it on the way."""
    del slots_ref                       # the index maps read it
    s_new = dec_ref[0, 0] * s_ref[0, 0] \
        + v_ref[0, 0] * pk_ref[0, 0]    # [d,1] x [1,tile] -> [d,tile]
    s_out_ref[0, 0] = s_new
    part = jax.lax.dot_general(
        pq_ref[0, 0], s_new, (((1,), (1,)), ((), ())),
        precision=PRECISION, preferred_element_type=F32)   # [G8, d]

    @pl.when(pl.program_id(2) == 0)
    def _():
        num_ref[0, 0] = part

    @pl.when(pl.program_id(2) > 0)
    def _():
        num_ref[0, 0] += part


# A jit of its own, so that a decode module traces and lowers the
# kernel once and not once a layer (PERF.md section 6, PR 26, finding
# 3).
@functools.partial(jax.jit, static_argnames=('tile', 'interpret'))
def _retention_decode(slots, pq, pk, v, dec, S, *, tile, interpret=False):
    """pq [R,Hkv,G8,D], pk [R,Hkv,1,D], v [R,Hkv,d,1], dec [R,Hkv,1,1],
    S [slots,Hkv,d,D], slots [R] int32 (distinct).  Returns the
    numerators [R,Hkv,G8,d] and S with the rows' slots rewritten."""
    R, hkv, g8, D = pq.shape
    d = v.shape[2]

    def row(r, j, t, slots):
        return (r, j, 0, 0)

    def feat(r, j, t, slots):
        return (r, j, 0, t)

    def slot(r, j, t, slots):
        return (slots[r], j, 0, t)

    num, S = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R, hkv, D // tile),
            in_specs=[pl.BlockSpec((1, 1, g8, tile), feat),
                      pl.BlockSpec((1, 1, 1, tile), feat),
                      pl.BlockSpec((1, 1, d, 1), row),
                      pl.BlockSpec((1, 1, 1, 1), row),
                      pl.BlockSpec((1, 1, d, tile), slot)],
            out_specs=[pl.BlockSpec((1, 1, g8, d), row),
                       pl.BlockSpec((1, 1, d, tile), slot)]),
        out_shape=[jax.ShapeDtypeStruct((R, hkv, g8, d), F32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)],
        # operand 5 (after the prefetched slots) is S; output 1 is S
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=('arbitrary',) * 3,
            vmem_limit_bytes=_vmem_bytes(d, g8, tile)),
        interpret=interpret,
        name='retention_decode',
    )(slots.astype(jnp.int32), pq, pk, v, dec, S)
    return num, S


def _decode_plain(slots, pq, pk, v, dec, S):
    """Gather the rows' slots, update, read, scatter back."""
    rows = dec[..., None, None] * S[slots] \
        + v[..., :, None] * pk[..., None, :]           # [R,Hkv,d,D]
    num = jnp.einsum('rjgD,rjcD->rjgc', pq, rows, precision=PRECISION)
    return num, S.at[slots].set(rows)


def retention_decode(q, k, v, g, S, z, slots, active):
    """One token a row.

    q [R,Hq,d], k, v [R,Hkv,d], g [R,Hkv] (log decay), S [slots,Hkv,d,D]
    and z [slots,Hkv,D] float32, slots [R] int (DISTINCT: a row that is
    padding names a slot no live row holds), active [R] bool.  Returns
    y [R,Hq,d] float32, S, z.  A row that is not active leaves its slot
    as it was, and its y means nothing.
    """
    with jax.named_scope('retention.decode'):
        R, hq, d = q.shape
        hkv = k.shape[1]
        G = hq // hkv
        live = active[:, None]
        dec = jnp.where(live, jnp.exp(g.astype(F32)), 1.0)   # [R,Hkv]
        pk = jnp.where(live[..., None], phi(k), 0.0)         # [R,Hkv,D]
        pq = phi(_grouped(q.astype(F32) * (1.0 / math.sqrt(d)), hkv))
        v = v.astype(F32)
        z_rows = dec[..., None] * z[slots] + pk
        den = jnp.einsum('rjgD,rjD->rjg', pq, z_rows,
                         precision=PRECISION)
        z = z.at[slots].set(z_rows)
        if can_use_pallas(S, q):
            # the MXU wants whole sublane tiles of query heads
            g8 = -(-G // 8) * 8
            pq8 = jnp.pad(pq, ((0, 0), (0, 0), (0, g8 - G), (0, 0)))
            num, S = _retention_decode(
                slots, pq8, pk[:, :, None, :], v[..., None],
                dec[..., None, None], S, tile=_tile(d),
                interpret=_gating.INTERPRET)
            num = num[:, :, :G]
        else:
            num, S = _decode_plain(slots, pq, pk, v, dec, S)
        y = num / (den[..., None] + EPS_R)
        return y.reshape(R, hq, d), S, z
