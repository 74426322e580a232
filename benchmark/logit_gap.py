"""The one comparison behind `correct` of both serving runners: how far
a token that was served lies under the float32 reference's best logit
at its position (tokens are never compared with tokens: at random
weights the best logit changes on rounding).  A runner brings its
reference as `logits_at(ids [B, T], positions [B, K]) -> [B, K, V]`
over weights the reference was given; `sample` draws the rows of a
window to hold, `gaps` reads them, `check` decides.

A row is (prompt, tokens): the ids a request was sent and the ids it
was served.  `judged` puts other tokens in the served ones' place at
the same positions of the same context: the control's, the reference
in the precision below choosing for itself (`first_choices`).
"""
import time

import numpy as np


def worst(gaps):
    """The largest gap as a plain number; one that is no number is the
    worst there is."""
    return float(np.nan_to_num(np.max(gaps), nan=np.inf))


def sample(requests, seed, count):
    """Of the requests a window finished whole, the longest and, drawn
    from the seed, `count` - 1 others, as (prompt, tokens).  Where a
    stall let none finish, the same of those it cut with tokens served;
    nothing only where the window served no token at all."""
    done = [r for r in requests if r.state == 'done'
            and len(r.tokens) == r.max_new_tokens] \
        or [r for r in requests if len(r.tokens) > 0]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens),
                                       r.rid))
    others = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 4])
    take = min(len(others), int(count) - 1)
    picked = [others[i] for i in sorted(rng.choice(
        len(others), size=take, replace=False))] if take else []
    return [(np.asarray(r.prompt), list(r.tokens))
            for r in [longest] + picked]


def ends(tokens, keep):
    """The indices of a row's tokens that are judged where only `keep`
    of them are: the first and the last `keep` / 2 (all, where the row
    has no more)."""
    n = len(tokens)
    if n <= keep:
        return np.arange(n)
    half = keep // 2
    return np.concatenate([np.arange(half), n - half + np.arange(half)])


def _blocks(rows, width, keep, block):
    """Right-padded ids, the positions that produced the judged tokens,
    which of a row's tokens those are, and which entries are live, for
    `block` rows at a time (one shape, one compilation)."""
    for lo in range(0, len(rows), block):
        part = rows[lo:lo + block]
        ids = np.zeros((block, width), np.int64)
        positions = np.zeros((block, keep), np.int64)
        picks = np.zeros((block, keep), np.int64)
        live = np.zeros((block, keep), bool)
        for i, (prompt, tokens) in enumerate(part):
            n, idx = len(prompt), ends(tokens, keep)
            ids[i, :n] = prompt
            ids[i, n:n + len(tokens) - 1] = tokens[:-1]
            # token j was chosen from the logits at position n - 1 + j
            positions[i, :idx.size] = n - 1 + idx
            picks[i, :idx.size] = idx
            live[i, :idx.size] = True
        yield lo, part, ids, positions, picks, live


def gaps(logits_at, rows, width, keep, block=2, judged=None,
         id_limit=None):
    """The gap of every judged token of every row under the
    reference's best logit at its position ([rows, keep], 0 where a row
    has fewer), how many of them are the reference's best, the
    reference's margins over its second best, and the spread of its
    logits over the first `id_limit` ids in the last block."""
    import jax
    import jax.numpy as jnp
    out = np.zeros((len(rows), keep), np.float32)
    margins, same, spread = [], 0, 0.0
    for lo, part, ids, positions, picks, live in _blocks(
            rows, width, keep, block):
        chosen = np.zeros_like(picks)
        for i, (_prompt, tokens) in enumerate(part):
            n = int(live[i].sum())
            chosen[i, :n] = judged[lo + i][:n] if judged is not None \
                else np.asarray(tokens)[picks[i, :n]]
        logits = logits_at(ids, positions)
        top2 = np.asarray(jax.lax.top_k(logits, 2)[0])
        took = np.asarray(jnp.take_along_axis(
            logits, jnp.asarray(chosen)[:, :, None], axis=2)[:, :, 0])
        gap = top2[:, :, 0] - took
        same += int((gap == 0)[live].sum())
        margins.append((top2[:, :, 0] - top2[:, :, 1])[live])
        spread = float(logits[:len(part), :, :id_limit].std())
        out[lo:lo + len(part)] = np.where(live, gap, 0.0)[:len(part)]
    return out, same, np.concatenate(margins), spread


def first_choices(logits_at, rows, width, keep, block=2):
    """For each row the token that `logits_at` puts first at every
    judged position: what the control hands `check` as `judged`."""
    out = []
    for _lo, part, ids, positions, _picks, live in _blocks(
            rows, width, keep, block):
        first = np.asarray(logits_at(ids, positions).argmax(-1))
        out += [first[i][live[i]] for i in range(len(part))]
    return out


def check(name, logits_at, rows, limit, say, compared, *, width, keep,
          block=2, judged=None, id_limit=None, what='rows'):
    """Holds `rows` to the reference by `limit`, says what it read and
    writes [worst gap, limit] into `compared[name]`.  Returns ok and
    the gaps."""
    t0 = time.monotonic()
    compared[name] = [float('inf'), float(limit)]
    if not rows:
        say(f'{name}: no token to hold against the reference')
        return False, None
    read, same, margin, spread = gaps(logits_at, rows, width, keep, block,
                                      judged, id_limit)
    judged_n = int(margin.size)
    say(f'{name}: {len(rows)} {what}, contexts '
        f'{[len(p) + len(t) for p, t in rows]}, {judged_n} tokens, worst '
        f'logit gap {read.max():.4f} (tol {limit}), per row '
        f'{[round(float(g), 4) for g in read.max(1)]}, {same} are the '
        f'reference\'s best, whose margin over its second is median '
        f'{np.median(margin):.4f}, least {margin.min():.4f}, and whose '
        f'logits spread {spread:.3f}; reference '
        f'{time.monotonic() - t0:.1f}s')
    compared[name] = [worst(read), float(limit)]
    return bool(np.isfinite(read).all() and read.max() <= limit), read
