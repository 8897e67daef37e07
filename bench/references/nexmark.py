"""The NEXmark configuration's own reference semantics (its ``"reference"``
file): the generator's event stream and queries 1, 2, 5 and 7 as Apache
Beam's Nexmark suite defines them (``NexmarkConfiguration`` defaults),
written from those definitions and the deployment's stated rules. It imports
nothing of the program. Batches are channel-major ``(8, B)``, as the plain
reference (``bench/lib/reference.py``) holds them, in its dtype; integers
are rebuilt from the channels and computed in int32.

Events: ``e = counter * B + i``; ``e % 50`` is 0 for a person, 1-3 for an
auction, else a bid. A bid's latest auction is ``3 * (e // 50) + 2``; with
probability 1/2 it names the first auction of the latest block of 100, else
one of ``[max(last - 100, 0), last + 10]`` uniformly. Ids start at 1000.
Price: ``round(10 ** (6 u) * 100)`` cents with a 16-bit ``u`` from the bits,
the float32 product of two tabulated factors rounded half to even. The
random bits are JAX's threefry, keyed by the source type and the counter;
the plain reference hands a source factory its batch alone, so this file
keys every ``nexmark`` source as ``nexmark`` (the collection has one).

Channels: 0-1 the event id split at 10**4, 2-3 the auction (a person's or
an auction's own id), 4-5 the price in cents, 6 the validity flag, 7 the
kind (0 person, 1 auction, 2 bid).

Q1 ``price * 89 // 100``; Q2 bids with ``auction % 123 == 0``; Q5 bids per
auction in sliding windows (10 s every 5 s at 10,000 events/s of event
time), the most-bid auctions of each window; Q7 the highest price of each
fixed 10 s window and the bids carrying it. A window closes at the first bid
past its end and emits then; a task's first window is what it saw from its
admission; ties ascend (auction id, bid id), 64 rows at most per window, each
row carrying the number tied in channel 7; the closed windows' rows fill a
step's batch from row 0 and the rest is zero. Q5 rows: window (its first
pane), count, auction, 0, 0, 1, tied; Q7 rows: window, bid id less the
window's first id, auction, price, 1, tied. Q5 counts per pane through
one-hot comparison into a table per pane, relative to a base below any
auction the pane can name; Q7 takes its ties by ``lax.top_k``.
"""
import hashlib

import jax
import jax.numpy as jnp
import numpy as np

SPLIT = 10_000
CAPACITY = 64
HI = np.array([100.0 * 10.0 ** (6.0 * j / 256) for j in range(256)], np.float32)
LO = np.array([10.0 ** (6.0 * j / 65536) for j in range(256)], np.float32)


def _int(x, hi: int, lo: int):
    return x[hi].astype(jnp.int32) * SPLIT + x[lo].astype(jnp.int32)


def _parts(v):
    return [(v // SPLIT).astype(jnp.float32), (v % SPLIT).astype(jnp.float32)]


def nexmark(batch):
    def emit(counter, dtype):
        seed = int.from_bytes(hashlib.sha256(b"nexmark").digest()[:4], "little")
        return _events(counter, batch, seed).astype(dtype)
    return emit


def _events(counter, batch, seed):
    e = counter.astype(jnp.int32) * batch + jnp.arange(batch, dtype=jnp.int32)
    w0, w1 = jax.random.bits(jax.random.fold_in(jax.random.PRNGKey(seed), counter),
                             (2, batch), jnp.uint32)
    epoch, off = e // 50, e % 50
    last = 3 * epoch + 2
    lowest = jnp.maximum(last - 100, 0)
    draw = (w0 >> 1) % (last - lowest + 11).astype(jnp.uint32)
    auction = 1000 + jnp.where(w0 % 2 == 1, last - last % 100, lowest + draw.astype(jnp.int32))
    k = (w1 >> 16).astype(jnp.int32)
    price = jnp.rint(jnp.asarray(HI)[k // 256] * jnp.asarray(LO)[k % 256]).astype(jnp.int32)
    is_bid = off >= 4
    entity = jnp.where(off == 0, 1000 + epoch,
                       jnp.where(is_bid, auction, 1000 + 3 * epoch + off - 1))
    kind = jnp.where(off == 0, 0, jnp.where(is_bid, 2, 1)).astype(jnp.float32)
    price = jnp.where(is_bid, price, 0)
    return jnp.stack([*_parts(e), *_parts(entity), *_parts(price),
                      jnp.ones((batch,), jnp.float32), kind])


def nx_bids(cfg):
    def apply(s, x):
        keep = (x[6] > 0.5) & (x[7] == 2)
        return s, jnp.where(keep[None, :], x, 0).astype(x.dtype)
    return None, apply


def nx_convert(cfg):
    def apply(s, x):
        # price * 89 // 100 without passing 2**31: 10**4 * 89 / 100 = 8900
        euros = x[4].astype(jnp.int32) * 8900 + x[5].astype(jnp.int32) * 89 // 100
        valid = x[6] > 0.5
        hi, lo = _parts(euros)
        y = x.at[4].set(jnp.where(valid, hi, x[4]).astype(x.dtype))
        return s, y.at[5].set(jnp.where(valid, lo, x[5]).astype(x.dtype))
    return None, apply


def nx_select(cfg):
    modulus = int(cfg.get("modulus", 123))

    def apply(s, x):
        keep = (x[6] > 0.5) & (_int(x, 2, 3) % modulus == 0)
        return s, jnp.where(keep[None, :], x, 0).astype(x.dtype)
    return None, apply


def _first_true(mask):
    """The positions of the first CAPACITY True entries, ascending (via
    ``lax.top_k`` of the negated positions), and how many there are."""
    pos = jnp.arange(mask.shape[0], dtype=jnp.int32)
    top, _ = jax.lax.top_k(jnp.where(mask, -pos, -mask.shape[0] - 1), CAPACITY)
    return -top, jnp.sum(mask.astype(jnp.int32))


def _place(blocks, batch):
    """Each block's first n rows (channel-major (8, CAPACITY) blocks), one
    after another from column 0, chosen by one-hot comparison; zeros after."""
    width = CAPACITY * len(blocks)
    col = jnp.arange(width, dtype=jnp.int32)
    out = jnp.zeros((8, width), jnp.float32)
    at = jnp.zeros((), jnp.int32)
    for block, n in blocks:
        rel = col - at
        hit = (rel[:, None] == jnp.arange(CAPACITY)[None, :]) & (rel[:, None] < n)
        out = out + jnp.sum(jnp.where(hit[None], block[:, None, :], 0.0), axis=2)
        at = at + n
    return jnp.concatenate([out, jnp.zeros((8, max(batch - width, 0)))], axis=1)[:, :batch]


def _panes(x, length):
    valid = x[6] > 0.5
    e = _int(x, 0, 1)
    idx = e // length
    first = jnp.min(jnp.where(valid, idx, 2 ** 31 - 1))
    last = jnp.max(jnp.where(valid, idx, -1))
    return valid, e, idx, ((first, last >= 0), (last, last > first))


def nx_hot_items(cfg):
    rate = int(cfg.get("rate", 10_000))
    size, period = int(cfg.get("size", 10)), int(cfg.get("period", 5))
    assert size == 2 * period
    pane = rate * period
    slots = 3 * ((pane + 49) // 50) + 256

    def base(p):  # at least 101 below the latest auction at the pane's first event
        return jnp.maximum(3 * ((p * pane) // 50) - 128, 0)

    def count(keys, valid):
        hit = (keys[None, :] == jnp.arange(slots, dtype=jnp.int32)[:, None]) & valid[None, :]
        return jnp.sum(hit.astype(jnp.int32), axis=1)

    def close(table, lowest, window):
        top = jnp.max(table)
        pos, tied = _first_true((table == top) & (top > 0))
        block = jnp.stack([jnp.full((CAPACITY,), window, jnp.float32),
                           jnp.full((CAPACITY,), top, jnp.float32),
                           *_parts(1000 + lowest + pos), jnp.zeros((CAPACITY,)),
                           jnp.zeros((CAPACITY,)), jnp.ones((CAPACITY,)),
                           jnp.full((CAPACITY,), tied, jnp.float32)])
        return block, jnp.where(top > 0, jnp.minimum(tied, CAPACITY), 0)

    def take(st, p_new, counts, present):
        p, prev, cur = st
        on = p >= 0
        lo_prev, lo_cur = base(p - 1), base(p)
        pad = jnp.zeros_like(cur)
        both = jnp.concatenate([prev, pad]) + jnp.roll(jnp.concatenate([cur, pad]),
                                                       lo_cur - lo_prev)
        a, n_a = close(both, lo_prev, p - 1)
        b, n_b = close(jnp.concatenate([cur, jnp.zeros_like(cur)]), lo_cur, p)
        n_a = jnp.where(present & on & (p_new >= p + 1), n_a, 0)
        n_b = jnp.where(present & on & (p_new >= p + 2), n_b, 0)
        if_same = on & (p_new == p)
        nxt = (p_new,
               jnp.where(if_same, prev, jnp.where(on & (p_new == p + 1), cur, jnp.zeros_like(cur))),
               jnp.where(if_same, cur + counts, counts))
        st = tuple(jnp.where(present, n, o) for n, o in zip(nxt, st))
        return st, [(a, n_a), (b, n_b)]

    def apply(st, x):
        valid, e, idx, ends = _panes(x, pane)
        rel = _int(x, 2, 3) - 1000 - base(idx)
        blocks = []
        for which, (p_new, present) in enumerate(ends):
            in_pane = valid & (idx == p_new) & present
            st, out = take(st, p_new, count(rel, in_pane), present)
            blocks += out
        return st, _place(blocks, x.shape[1]).astype(x.dtype)

    def init(dtype):
        return (jnp.full((), -1, jnp.int32), jnp.zeros((slots,), jnp.int32),
                jnp.zeros((slots,), jnp.int32))
    return init, apply


def nx_highest_bid(cfg):
    rate, size = int(cfg.get("rate", 10_000)), int(cfg.get("size", 10))
    length = rate * size

    def take(st, w_new, top, ids, aucs, tied, present):
        w, best, n, held_ids, held_aucs = st
        on = w >= 0
        block = jnp.stack([jnp.full((CAPACITY,), w, jnp.float32),
                           (held_ids - w * length).astype(jnp.float32), *_parts(held_aucs),
                           *[jnp.full((CAPACITY,), v, jnp.float32) for v in _parts(best)],
                           jnp.ones((CAPACITY,)), jnp.full((CAPACITY,), n, jnp.float32)])
        n_out = jnp.where(present & on & (w_new > w), jnp.minimum(n, CAPACITY), 0)
        keep = jnp.minimum(n, CAPACITY)
        j = jnp.arange(CAPACITY)
        later = jnp.clip(j - keep, 0, CAPACITY - 1)
        joined = (w, best, n + tied, jnp.where(j < keep, held_ids, ids[later]),
                  jnp.where(j < keep, held_aucs, aucs[later]))
        fresh = (w_new, top, tied, ids, aucs)
        restart = ~on | (w_new > w) | (top > best)
        nxt = tuple(jnp.where(restart, f, jnp.where(top == best, m, o))
                    for f, m, o in zip(fresh, joined, st))
        st = tuple(jnp.where(present, a, b) for a, b in zip(nxt, st))
        return st, (block, n_out)

    def apply(st, x):
        valid, e, idx, ends = _panes(x, length)
        price, auction = _int(x, 4, 5), _int(x, 2, 3)
        blocks = []
        for w_new, present in ends:
            inside = valid & (idx == w_new)
            top = jnp.max(jnp.where(inside, price, -1))
            pos, tied = _first_true(inside & (price == top))
            st, block = take(st, w_new, top, e[pos], auction[pos], tied, present)
            blocks.append(block)
        return st, _place(blocks, x.shape[1]).astype(x.dtype)

    def init(dtype):
        return (jnp.full((), -1, jnp.int32), jnp.full((), -1, jnp.int32),
                jnp.zeros((), jnp.int32), jnp.zeros((CAPACITY,), jnp.int32),
                jnp.zeros((CAPACITY,), jnp.int32))
    return init, apply


TASKS = {"nx_bids": nx_bids, "nx_convert": nx_convert, "nx_select": nx_select,
         "nx_hot_items": nx_hot_items, "nx_highest_bid": nx_highest_bid}
SOURCES = {"nexmark": nexmark}
