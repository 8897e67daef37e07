"""Plain reference of the NEXmark generator and queries 1, 2, 5 and 7, one
event at a time, in Python integers and float64 (numpy), written from Apache
Beam's Nexmark definitions (``NexmarkConfiguration`` defaults). It imports
nothing of the program; only the random bits come from JAX's threefry, keyed
as the program's generator keys them, since the deployment states them so.

Generator (Beam's ``NexmarkUtils``, ``BidGenerator``, ``PriceGenerator``):
event ``e`` is a person when ``e % 50 == 0``, an auction when ``e % 50`` is
1 to 3, else a bid. A bid's latest auction is ``3 * (e // 50) + 2``
(``lastBase0AuctionId``). With probability 1/2 (``nextInt(hotAuctionRatio =
2) > 0``) it bids on the first auction of the latest block of 100; else on
an auction drawn from ``[max(last - 100, 0), last + 10]`` (100 in flight
plus Beam's lead of 10). Ids start at 1000. The price is ``round(10 ** (6 u)
* 100)`` cents with a 16-bit ``u``: the float32 product of two tabulated
factors, rounded half to even.

Queries: Q1 ``price * 89 // 100``; Q2 ``auction % 123 == 0``; Q5 bids per
auction in sliding windows (10 s every 5 s), the auction(s) with the most
bids; Q7 the highest price in fixed 10 s windows, the bid(s) carrying it.
Windows follow the deployment's rules: a row's pane or window is
``e // (rate * seconds)``; a window closes at the first bid past its end
and emits at that step; a task's first window holds what it saw from its
admission; ties ascend (auction id, bid id), at most 64 rows per window,
each row carrying the number tied; a step's rows come first in window order,
the rest of the batch zero.
"""
from __future__ import annotations

import hashlib
import json
from typing import Dict, List, Sequence, Tuple

import numpy as np

SPLIT = 10_000
CAPACITY = 64
HI = [100.0 * 10.0 ** (6.0 * j / 256) for j in range(256)]
LO = [10.0 ** (6.0 * j / 65536) for j in range(256)]


def split(v: int) -> Tuple[int, int]:
    return v // SPLIT, v % SPLIT


def join(hi: float, lo: float) -> int:
    return int(hi) * SPLIT + int(lo)


def random_bits(source_type: str, counter: int, batch: int) -> np.ndarray:
    """(2, batch) uint32: threefry bits keyed by the source type and counter."""
    import jax

    seed = int.from_bytes(hashlib.sha256(source_type.encode()).digest()[:4], "little")
    key = jax.random.fold_in(jax.random.PRNGKey(seed), counter)
    return np.asarray(jax.random.bits(key, (2, batch), np.uint32))


def generate(source_type: str, counter: int, batch: int) -> np.ndarray:
    """The source's batch at ``counter``, (batch, 8) float64."""
    bits = random_bits(source_type, counter, batch)
    out = np.zeros((batch, 8))
    for i in range(batch):
        e = counter * batch + i
        epoch, off = divmod(e, 50)
        price = 0
        if off == 0:
            kind, entity = 0, 1000 + epoch
        elif off < 4:
            kind, entity = 1, 1000 + 3 * epoch + off - 1
        else:
            kind = 2
            w0, w1 = int(bits[0, i]), int(bits[1, i])
            last = 3 * epoch + 2
            if w0 & 1:
                auction = (last // 100) * 100
            else:
                lowest = max(last - 100, 0)
                auction = lowest + (w0 >> 1) % (last - lowest + 1 + 10)
            entity = 1000 + auction
            k = w1 >> 16
            price = int(np.rint(np.float32(HI[k >> 8]) * np.float32(LO[k & 255])))
        out[i] = [*split(e), *split(entity), *split(price), 1.0, kind]
    return out


# -- queries, one row at a time ---------------------------------------------------------

def bids(x: np.ndarray) -> np.ndarray:
    y = np.zeros_like(x)
    for i, row in enumerate(x):
        if row[6] > 0.5 and row[7] == 2:
            y[i] = row
    return y


def convert(x: np.ndarray) -> np.ndarray:
    y = x.copy()
    for i, row in enumerate(x):
        if row[6] > 0.5:
            y[i, 4:6] = split(join(row[4], row[5]) * 89 // 100)
    return y


def select(x: np.ndarray, modulus: int = 123) -> np.ndarray:
    y = np.zeros_like(x)
    for i, row in enumerate(x):
        if row[6] > 0.5 and join(row[2], row[3]) % modulus == 0:
            y[i] = row
    return y


def _emit(rows: List[List[float]], batch: int) -> np.ndarray:
    out = np.zeros((batch, 8))
    rows = rows[:batch]
    if rows:
        out[: len(rows)] = rows
    return out


class HotItems:
    """Q5: per-pane counts of bids per auction; window k = panes k and k+1."""

    def __init__(self, rate: int = 10_000, size: int = 10, period: int = 5):
        assert size == 2 * period
        self.pane_events = rate * period
        self.counts: Dict[int, Dict[int, int]] = {}
        self.next_window = None  # the lowest window not closed yet

    def _close(self, k: int, rows: List[List[float]]) -> None:
        total: Dict[int, int] = {}
        for pane in (k, k + 1):
            for a, n in self.counts.get(pane, {}).items():
                total[a] = total.get(a, 0) + n
        if not total:
            return
        top = max(total.values())
        tied = sorted(a for a, n in total.items() if n == top)
        for a in tied[:CAPACITY]:
            rows.append([k, top, *split(a), 0, 0, 1, len(tied)])

    def __call__(self, x: np.ndarray) -> np.ndarray:
        rows: List[List[float]] = []
        for row in x:
            if row[6] <= 0.5:
                continue
            q = join(row[0], row[1]) // self.pane_events
            if self.next_window is None:
                self.next_window = q - 1
            # windows k with k + 2 <= q have ended; close those with bids
            ended = sorted({k for p in self.counts for k in (p - 1, p)
                            if self.next_window <= k <= q - 2})
            for k in ended:
                self._close(k, rows)
            self.next_window = max(self.next_window, q - 1)
            for p in [p for p in self.counts if p < self.next_window]:
                del self.counts[p]
            a = join(row[2], row[3])
            pane = self.counts.setdefault(q, {})
            pane[a] = pane.get(a, 0) + 1
        return _emit(rows, x.shape[0])


class HighestBid:
    """Q7: the highest price of each fixed window and the bids carrying it."""

    def __init__(self, rate: int = 10_000, size: int = 10):
        self.window_events = rate * size
        self.window = None
        self.best = -1
        self.tied: List[Tuple[int, int]] = []  # (bid id, auction), ascending

    def __call__(self, x: np.ndarray) -> np.ndarray:
        rows: List[List[float]] = []
        for row in x:
            if row[6] <= 0.5:
                continue
            e, a, price = join(row[0], row[1]), join(row[2], row[3]), join(row[4], row[5])
            w = e // self.window_events
            if self.window is not None and w > self.window:
                for bid, auction in self.tied[:CAPACITY]:
                    rows.append([self.window, bid - self.window * self.window_events,
                                 *split(auction), *split(self.best), 1, len(self.tied)])
            if self.window is None or w > self.window or price > self.best:
                self.window, self.best, self.tied = w, price, [(e, a)]
            elif price == self.best:
                self.tied.append((e, a))
        return _emit(rows, x.shape[0])


def make_task(typ: str, cfg: dict):
    """``apply(x) -> y`` of one running task (stateful ones keep their state)."""
    if typ == "nx_bids":
        return bids
    if typ == "nx_convert":
        return convert
    if typ == "nx_select":
        return lambda x: select(x, int(cfg.get("modulus", 123)))
    if typ == "nx_hot_items":
        return HotItems(**{k: int(v) for k, v in cfg.items()})
    if typ == "nx_highest_bid":
        return HighestBid(**{k: int(v) for k, v in cfg.items()})
    raise KeyError(typ)


# -- a run: tasks shared by prefix, as the deployment's reuse guarantee states ------------

def prefixes(source: str, steps: Sequence[Tuple[str, dict]], sink: str) -> List[Tuple]:
    keys, cur = [], (("source", source),)
    keys.append(cur)
    for typ, cfg in steps:
        cur = cur + ((typ, json.dumps(cfg, sort_keys=True)),)
        keys.append(cur)
    keys.append(cur + (("sink", sink),))
    return keys


def run(flows: Dict[str, Tuple[str, list, str]], log: Sequence[Tuple[int, str, str]],
        steps: int, batch: int, starts: Dict[Tuple[str, int], int] = None):
    """Every running dataflow's sink after ``steps`` steps: ``{name: (count,
    checksum, last)}``. ``flows`` maps a name to (source, steps, sink);
    ``log`` holds (step, "submit"|"remove", name); a source started at step
    ``s`` begins at ``starts[(source, s)]`` (0 unless given)."""
    starts = starts or {}
    users: Dict[Tuple, int] = {}
    born: Dict[Tuple, int] = {}
    tasks: Dict[Tuple, object] = {}
    sinks: Dict[Tuple, Tuple[int, float, np.ndarray]] = {}
    running: Dict[str, List[Tuple]] = {}
    for s in range(steps):
        for step, op, name in log:
            if step != s:
                continue
            keys = prefixes(*flows[name])
            if op == "submit":
                running[name] = keys
                for k in keys:
                    if users.get(k, 0) == 0:
                        born[k] = s
                        if len(k) > 1 and k[-1][0] != "sink":
                            tasks[k] = make_task(k[-1][0], json.loads(k[-1][1]))
                    users[k] = users.get(k, 0) + 1
            else:
                del running[name]
                for k in keys:
                    users[k] -= 1
                    if users[k] == 0:
                        del born[k]
                        tasks.pop(k, None)
                        sinks.pop(k, None)
        out: Dict[Tuple, np.ndarray] = {}
        for keys in running.values():
            for k in keys:
                if k in out:
                    continue
                typ, cfg = k[-1]
                if typ == "source":
                    counter = starts.get((cfg, born[k]), 0) + s - born[k]
                    out[k] = generate(cfg, counter, batch)
                elif typ == "sink":
                    x = out[k[:-1]]
                    n, total, _ = sinks.get(k, (0, 0.0, None))
                    sinks[k] = (n + 1, total * 0.5 + x.sum(), x)
                    out[k] = x
                else:
                    out[k] = tasks[k](out[k[:-1]])
    return {name: sinks[keys[-1]] for name, keys in running.items()}
