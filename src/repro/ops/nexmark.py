"""NEXmark on the stream path: the generator's event stream and queries 1, 2,
5 and 7 (Tucker, Tufte, Papadimos, Maier 2008), as Apache Beam's Nexmark
suite defines them with the ``NexmarkConfiguration`` defaults.

Source ``nexmark`` (any ``nexmark:<suffix>`` names another seeded stream):
its state is the int32 step counter ``c`` and its batch a pure function of
it, so a shared source emits what each tenant would see alone. Row ``i`` is
the event ``e = c * B + i``; out of every 50 ids one is a person, three are
auctions and 46 are bids (``e % 50``). A bid's auction: with probability 1/2
the first of the latest block of 100 auctions (the hot auction), else one
drawn uniformly from the last 100 auctions and the 10 after them. Its price
is ``round(10 ** (6 u) * 100)`` cents. Event time is ``e / rate``, events in
order. Random bits come from JAX's threefry keyed by the source type and
``c``; ``u`` has 16 bits, ``k = 256 k1 + k0``, and the price is the float32
product ``PRICE_HI[k1] * PRICE_LO[k0]`` rounded to the nearest integer
(half to even), so the chip and a float64 reference agree to the cent.

Channels, every value an integer held exactly in float32:

  0, 1  event id split: ``e // 10**4``, ``e % 10**4``
  2, 3  auction id split (a person event: the person's id; an auction
        event: its own id)
  4, 5  price in cents split, bids only
  6     validity flag
  7     event kind: 0 person, 1 auction, 2 bid

Event ids stay below 2**31 while ``c * B`` does (counters below 131072 at
16384 events per step), auction ids below 10**8, prices below 2**27: each is
rebuilt as ``hi * 10**4 + lo`` in int32 and split again, and no product
passes 2**31.

Tasks (each body under ``jax.named_scope("nexmark/<type>")``):

  * ``nx_bids``: the bids; other rows become zero rows (flag 0).
  * ``nx_convert`` (Q1): price to euros, ``price * 89 // 100`` in cents.
  * ``nx_select`` (Q2): the bids whose ``auction % modulus == 0`` (123).
  * ``nx_hot_items`` (Q5): bids per auction in sliding windows of ``size``
    seconds every ``period`` (10, 5) at ``rate`` events per second of event
    time (10,000); each closing window emits the auction(s) with the most
    bids.
  * ``nx_highest_bid`` (Q7): the highest price in fixed windows of ``size``
    seconds (10); each closing window emits the bid(s) that carry it.

Window rules. A row's pane (Q5) or window (Q7) is ``e // (rate * length)``
in integers. A window closes at the first bid past its end and emits at that
step; a task's first window is the partial one it saw from its admission.
Ties are emitted in ascending auction id (Q5) or bid id (Q7), at most
``CAPACITY`` rows per window; every emitted row carries the number tied in
channel 7, so a window that reached the capacity shows. A window task emits
its batch's ``B`` rows: the closed windows' rows first, in window order,
the rest zero rows (flag 0). Rows of one batch are consecutive events (a
chain from the source), and a pane or window is at least a batch long
(checked at trace time), so a batch touches at most two of them.

Output rows of Q5: ``[window, count, auction hi, auction lo, 0, 0, 1, tied]``,
where ``window`` is the index of the window's first pane. Of Q7: ``[window,
e - window * rate * size, auction hi, auction lo, price hi, price lo, 1,
tied]``.

Both window tasks count in their state the valid rows fed to them (``rows``,
int32, wrapping past 2**31), which the backend reads into
``repro_ops_window_rows_total``.

Q5 keeps one count table per open pane, keyed by the auction relative to
the pane (:func:`pane_base`): the generator draws a pane's auctions from a
range of ``3 * L / 50 + 113`` ids (``L`` the pane's events), so
:func:`table_size` slots hold them; a key outside is not counted, which this
generator never causes. Windows combine the two panes' tables.
"""
from __future__ import annotations

import hashlib
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import window as kw

from .base import Operator, register, stateless
from .costs import NEXMARK_COSTS, SOURCE_COST

SPLIT = 10_000
EPOCH = 50  # events per generator epoch: 1 person, 3 auctions, 46 bids
PERSONS, AUCTIONS = 1, 3
FIRST_PERSON_ID = FIRST_AUCTION_ID = 1000
HOT_BLOCK = 100  # the hot auction is the first of the latest block of 100
IN_FLIGHT = 100  # a cold bid picks among the latest 100 auctions ...
LEAD = 10  # ... and the 10 after them
KIND_PERSON, KIND_AUCTION, KIND_BID = 0, 1, 2
CAPACITY = 64  # rows emitted per window at most
FLAG = 6

PRICE_HI = np.array([100.0 * 10.0 ** (6.0 * j / 256) for j in range(256)], np.float32)
PRICE_LO = np.array([10.0 ** (6.0 * j / 65536) for j in range(256)], np.float32)

_INT_MAX = np.iinfo(np.int32).max


def _seed_for(type_name: str) -> int:
    return int.from_bytes(hashlib.sha256(type_name.encode()).digest()[:4], "little")


def _join(hi: jnp.ndarray, lo: jnp.ndarray) -> jnp.ndarray:
    return hi.astype(jnp.int32) * SPLIT + lo.astype(jnp.int32)


def _split(v: jnp.ndarray):
    return (v // SPLIT).astype(jnp.float32), (v % SPLIT).astype(jnp.float32)


def _select(table: np.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """``table[idx]`` for a small table, by one-hot comparison (exact: one
    term of the sum is not zero)."""
    hit = idx[:, None] == jnp.arange(table.shape[0], dtype=idx.dtype)[None, :]
    return jnp.sum(jnp.where(hit, jnp.asarray(table)[None, :], 0.0), axis=1)


def make_source(type_name: str, batch: int = 32) -> Operator:
    """The NEXmark generator: events ``c * batch`` to ``(c + 1) * batch - 1``."""
    seed = _seed_for(type_name)

    def init_state(batch_: int):
        return jnp.zeros((), dtype=jnp.int32)

    def apply(counter, x=None):
        with jax.named_scope("nexmark/source"):
            e = counter * batch + jnp.arange(batch, dtype=jnp.int32)
            bits = jax.random.bits(jax.random.fold_in(jax.random.PRNGKey(seed), counter),
                                   (2, batch), jnp.uint32)
            epoch, off = e // EPOCH, e % EPOCH
            kind = jnp.where(off < PERSONS, KIND_PERSON,
                             jnp.where(off < PERSONS + AUCTIONS, KIND_AUCTION, KIND_BID))
            last = epoch * AUCTIONS + (AUCTIONS - 1)  # a bid's latest auction
            lowest = jnp.maximum(last - IN_FLIGHT, 0)
            span = (last - lowest + 1 + LEAD).astype(jnp.uint32)
            cold = lowest + ((bits[0] >> 1) % span).astype(jnp.int32)
            hot = (last // HOT_BLOCK) * HOT_BLOCK
            auction = FIRST_AUCTION_ID + jnp.where((bits[0] & 1) == 1, hot, cold)
            k = (bits[1] >> 16).astype(jnp.int32)
            price = jnp.round(_select(PRICE_HI, k >> 8) * _select(PRICE_LO, k & 255))
            price = jnp.where(kind == KIND_BID, price.astype(jnp.int32), 0)
            entity = jnp.where(
                kind == KIND_PERSON, FIRST_PERSON_ID + epoch,
                jnp.where(kind == KIND_AUCTION, FIRST_AUCTION_ID + epoch * AUCTIONS + off - PERSONS,
                          auction))
            cols = [*_split(e), *_split(entity), *_split(price),
                    jnp.ones((batch,), jnp.float32), kind.astype(jnp.float32)]
            return counter + 1, jnp.stack(cols, axis=1)

    return Operator(type=type_name, init_state=init_state, apply=apply,
                    cost_weight=SOURCE_COST, is_source=True)


def _stateless(type_name: str, fn) -> Operator:
    def scoped(x):
        with jax.named_scope(f"nexmark/{type_name}"):
            return fn(x)

    return stateless(type_name, scoped, cost=NEXMARK_COSTS[type_name])


@register("nx_bids")
def bids(cfg: Dict[str, Any]) -> Operator:
    """The bid events; every other row becomes a zero row."""

    def fn(x):
        keep = (x[:, FLAG] > 0.5) & (x[:, 7] == KIND_BID)
        return jnp.where(keep[:, None], x, 0.0)

    return _stateless("nx_bids", fn)


@register("nx_convert")
def convert(cfg: Dict[str, Any]) -> Operator:
    """Q1: each bid's price in euros, ``price * 89 // 100`` cents (integer
    division, as Beam's ``(price * 89) / 100`` on longs). With ``price = hi *
    10**4 + lo`` that is ``hi * 8900 + lo * 89 // 100``, all in int32."""

    def fn(x):
        hi, lo = x[:, 4].astype(jnp.int32), x[:, 5].astype(jnp.int32)
        euros = hi * (SPLIT // 100 * 89) + lo * 89 // 100
        e_hi, e_lo = _split(euros)
        valid = x[:, FLAG] > 0.5
        return x.at[:, 4].set(jnp.where(valid, e_hi, x[:, 4])).at[:, 5].set(
            jnp.where(valid, e_lo, x[:, 5]))

    return _stateless("nx_convert", fn)


@register("nx_select")
def select(cfg: Dict[str, Any]) -> Operator:
    """Q2: the bids whose auction id is a multiple of ``modulus`` (123);
    every other row becomes a zero row."""
    modulus = int(cfg.get("modulus", 123))

    def fn(x):
        keep = (x[:, FLAG] > 0.5) & (_join(x[:, 2], x[:, 3]) % modulus == 0)
        return jnp.where(keep[:, None], x, 0.0)

    return _stateless("nx_select", fn)


# -- keyed event-time windows ----------------------------------------------------------

def table_size(pane_events: int) -> int:
    """Slots of a Q5 pane table: a pane of ``L`` events sees auctions in a
    range of at most ``3 * ((L - 1) // 50 + 1) + 114`` ids from its base,
    rounded up to the count kernel's block."""
    need = AUCTIONS * ((pane_events - 1) // EPOCH + 1) + IN_FLIGHT + LEAD + AUCTIONS + 1
    return -(-need // kw.SLOT_BLOCK) * kw.SLOT_BLOCK


def pane_base(pane: jnp.ndarray, pane_events: int) -> jnp.ndarray:
    """The lowest auction id (less ``FIRST_AUCTION_ID``) a bid of ``pane``
    can name: 101 below the latest auction at the pane's first event."""
    return jnp.maximum(AUCTIONS * ((pane * pane_events) // EPOCH) - IN_FLIGHT - 1, 0)


def _segments(x: jnp.ndarray, length: int):
    """Per row: valid, event id, window (or pane) index and segment (0: the
    batch's first window, 1: the next, -1: not a valid row); each segment's
    window index and whether it has rows."""
    if length < x.shape[0]:
        raise ValueError(f"a window or pane of {length} events is shorter than the "
                         f"batch of {x.shape[0]}: raise the rate or the period")
    valid = x[:, FLAG] > 0.5
    e = _join(x[:, 0], x[:, 1])
    idx = e // length
    lo = jnp.min(jnp.where(valid, idx, _INT_MAX))
    hi = jnp.max(jnp.where(valid, idx, -1))
    seg = jnp.where(valid, jnp.where(idx == lo, 0, 1), -1)
    return valid, e, idx, seg, (lo, hi), (hi >= 0, hi > lo)


def _take_rows(state, valid):
    """The state without its ``rows`` counter, and the counter with this
    batch's valid rows added."""
    rest = {k: v for k, v in state.items() if k != "rows"}
    return rest, state["rows"] + jnp.sum(valid.astype(jnp.int32))


def _row(*cols) -> jnp.ndarray:
    return jnp.stack([jnp.broadcast_to(jnp.asarray(c, jnp.float32), (CAPACITY,)) for c in cols],
                     axis=1)


@register("nx_hot_items")
def hot_items(cfg: Dict[str, Any]) -> Operator:
    """Q5: bids per auction in sliding windows, the most-bid auction(s) of
    each window as it closes (module docstring).

    State: the last pane with a bid (``pane``, -1 before any), its count
    table (``cur``) and the one before it (``prev``), each relative to its
    pane's base, and ``rows``. A step counts the batch's bids per (pane,
    auction) with the ``window_count`` kernel, then takes its two panes in
    order: a later pane
    closes window ``pane - 1`` (``prev`` + ``cur``) and, when a pane was
    skipped, window ``pane`` too. Pane and window indexes, counts and
    auction ids are int32; emitted values are below 2**24."""
    rate = int(cfg.get("rate", 10_000))
    size, period = int(cfg.get("size", 10)), int(cfg.get("period", 5))
    if size != 2 * period:
        raise ValueError("nx_hot_items takes windows of two periods (size = 2 * period)")
    pane_events = rate * period
    slots = table_size(pane_events)

    def init_state(batch: int):
        return {"pane": jnp.full((), -1, jnp.int32),
                "prev": jnp.zeros((slots,), jnp.int32),
                "cur": jnp.zeros((slots,), jnp.int32),
                "rows": jnp.zeros((), jnp.int32)}

    def winners(table, base, window):
        """Rows of one window: its most-bid auctions, ascending, and how many."""
        top = jnp.max(table)
        mask = (table == top) & (top > 0)
        pos, tied = kw.first_positions(mask, CAPACITY)
        hi, lo = _split(FIRST_AUCTION_ID + base + pos)
        rows = _row(window, top, hi, lo, 0.0, 0.0, 1.0, tied)
        return rows, jnp.minimum(tied, CAPACITY)

    def take(state, pane, counts, present):
        """Fold one pane's counts into the state; the closed windows' rows."""
        p, prev, cur = state["pane"], state["prev"], state["cur"]
        started = p >= 0
        b_prev, b_cur = pane_base(p - 1, pane_events), pane_base(p, pane_events)
        both = jnp.concatenate([prev, jnp.zeros_like(cur)]) + jax.lax.dynamic_update_slice(
            jnp.zeros((2 * slots,), jnp.int32), cur, (b_cur - b_prev,))
        rows_a, n_a = winners(both, b_prev, p - 1)
        rows_b, n_b = winners(jnp.concatenate([cur, jnp.zeros_like(cur)]), b_cur, p)
        n_a = jnp.where(present & started & (pane > p), n_a, 0)
        n_b = jnp.where(present & started & (pane >= p + 2), n_b, 0)
        same = started & (pane == p)
        new_prev = jnp.where(same, prev, jnp.where(started & (pane == p + 1), cur, 0))
        new = {"pane": pane, "prev": new_prev, "cur": jnp.where(same, cur + counts, counts)}
        state = jax.tree_util.tree_map(lambda a, b: jnp.where(present, a, b), new, state)
        return state, [(rows_a, n_a), (rows_b, n_b)]

    def apply(state, x):
        with jax.named_scope("nexmark/nx_hot_items"):
            valid, e, pane, seg, (lo, hi), (has_lo, has_hi) = _segments(x, pane_events)
            state, seen = _take_rows(state, valid)
            rel = _join(x[:, 2], x[:, 3]) - FIRST_AUCTION_ID - pane_base(pane, pane_events)
            keys = jnp.where(valid & (rel >= 0) & (rel < slots), seg * slots + rel, -1)
            counts = kw.pane_counts(keys, 2 * slots)
            state, first = take(state, lo, counts[:slots], has_lo)
            state, second = take(state, hi, counts[slots:], has_hi)
            return dict(state, rows=seen), kw.pack_rows(first + second, x.shape[0])

    return Operator("nx_hot_items", init_state, apply,
                    cost_weight=NEXMARK_COSTS["nx_hot_items"], window=True)


@register("nx_highest_bid")
def highest_bid(cfg: Dict[str, Any]) -> Operator:
    """Q7: the highest price in fixed windows, the bid(s) that carry it as
    each window closes (module docstring).

    State: the window of the last bid (``window``, -1 before any), its
    highest price so far (``best``), how many bids carry it (``tied``) and
    the first ``CAPACITY`` of them (event ids and auctions, ascending), and
    ``rows``. A step finds each of its (at most two) windows' highest price
    (an int32 max), picks the bids that carry it, and folds them in: a later
    window closes the held one. Ids, prices and counts are int32;
    emitted values are below 2**24."""
    rate, size = int(cfg.get("rate", 10_000)), int(cfg.get("size", 10))
    window_events = rate * size

    def init_state(batch: int):
        return {"window": jnp.full((), -1, jnp.int32), "best": jnp.full((), -1, jnp.int32),
                "tied": jnp.zeros((), jnp.int32),
                "ids": jnp.zeros((CAPACITY,), jnp.int32),
                "auctions": jnp.zeros((CAPACITY,), jnp.int32),
                "rows": jnp.zeros((), jnp.int32)}

    def take(state, window, top, ids, auctions, tied, present):
        w, best, n = state["window"], state["best"], state["tied"]
        started = w >= 0
        a_hi, a_lo = _split(state["auctions"])
        p_hi, p_lo = _split(jnp.maximum(best, 0))
        rows = _row(w, state["ids"] - w * window_events, a_hi, a_lo, p_hi, p_lo, 1.0, n)
        n_out = jnp.where(present & started & (window > w), jnp.minimum(n, CAPACITY), 0)
        same = started & (window == w)
        held = jnp.minimum(n, CAPACITY)
        first = jnp.arange(CAPACITY) < held  # the held ties stay ahead of the new ones
        appended = {"ids": jnp.where(first, state["ids"], jnp.roll(ids, held)),
                    "auctions": jnp.where(first, state["auctions"], jnp.roll(auctions, held))}
        fresh = {"window": window, "best": top, "tied": tied, "ids": ids, "auctions": auctions}
        merged = dict(appended, window=w, best=best, tied=n + tied)
        new = jax.tree_util.tree_map(
            lambda f, m, s: jnp.where(~same | (top > best), f, jnp.where(top == best, m, s)),
            fresh, merged, state)
        state = jax.tree_util.tree_map(lambda a, b: jnp.where(present, a, b), new, state)
        return state, [(rows, n_out)]

    def apply(state, x):
        with jax.named_scope("nexmark/nx_highest_bid"):
            valid, e, window, seg, ends, present = _segments(x, window_events)
            state, seen = _take_rows(state, valid)
            price = _join(x[:, 4], x[:, 5])
            auction = _join(x[:, 2], x[:, 3])
            top = kw.segment_max(price, seg)
            out = []
            for s in (0, 1):
                pos, tied = kw.first_positions((seg == s) & (price == top[s]), CAPACITY)
                state, rows = take(state, ends[s], top[s], kw.pick(e, pos),
                                   kw.pick(auction, pos), tied, present[s])
                out += rows
            return dict(state, rows=seen), kw.pack_rows(out, x.shape[0])

    return Operator("nx_highest_bid", init_state, apply,
                    cost_weight=NEXMARK_COSTS["nx_highest_bid"], window=True)

