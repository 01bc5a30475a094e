"""The one traffic generator: YCSB-style keys, scrambled Zipfian picks and
Poisson arrivals, driven by a mix file of parameters and a seed.

A configuration (``bench/configs/<name>.json``) names the data: one set
of ``elements`` YCSB keys (``set_name``), or a ``sets`` block of many,
whose members :func:`data` draws from the seed.  A mix
(``bench/traffic/<name>.json``) names shares of operation kinds
(:data:`KINDS`), the page-length bounds, the key distribution and, for
many sets, how a request picks its set and which groups a join's sides
come from.  The cell (``bench/cells/<name>.json``) fixes the offered
rate.  From those and ``--seed`` this module draws a schedule: one
:class:`Op` per request, each with the offset at which it is due.

Every seed of a cell shares one arrival schedule: the due times
(exponential gaps scaled to fill the window exactly), the kind of each
request and each range's length, drawn once from a fixed seed.  The seed
draws the data set-up removes and every key.  Seeds that reordered the
arrivals moved a tail by more than the benchmark could bound: at 80% load
the p95 follows the clumps in the arrival order, not the system.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
MASK64 = (1 << 64) - 1
# the seed of the shared arrival schedule: due times, kinds, lengths
BASE_SEED = 0x5EED
KINDS = ("range", "probe", "count", "join", "insert", "remove")
# rng_for streams of a run's seed.  A mix or configuration without the
# newer keys draws from the first two alone, as it always did.
REMOVES, KEYS, MEMBERS, SET_PICKS, RIGHT_PICKS = range(5)


def fnvhash64(val: int) -> int:
    """YCSB's ``Utils.fnvhash64``: FNV-1a over the 8 little-endian bytes of
    a Java long, then ``Math.abs`` of the signed result."""
    h = FNV_OFFSET_BASIS_64
    for _ in range(8):
        h ^= val & 0xFF
        val >>= 8
        h = (h * FNV_PRIME_64) & MASK64
    if h >= 1 << 63:
        h = (1 << 64) - h
    return h


def ycsb_key(keynum: int) -> bytes:
    """YCSB core workload key with ``insertorder=hashed``: ``user<hash>``."""
    return b"user%d" % fnvhash64(keynum)


def ycsb_keys(n: int, start: int = 0) -> List[bytes]:
    keys = [ycsb_key(i) for i in range(start, start + n)]
    if len(set(keys)) != len(keys):
        raise ValueError(f"fnvhash64 collided among keys {start}..{start + n}")
    return keys


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, purpose); any int seed works."""
    return np.random.default_rng([seed % (1 << 64), stream])


class Zipfian:
    """YCSB's ``ZipfianGenerator`` (Gray et al., "Quickly generating
    billion-record synthetic databases"), vectorised over numpy."""

    def __init__(self, n: int, theta: float):
        self.n = n
        self.theta = theta
        ranks = np.arange(1, n + 1, dtype=np.float64)
        self.zetan = float(np.sum(ranks ** -theta))
        zeta2 = 1.0 + 0.5 ** theta
        self.alpha = 1.0 / (1.0 - theta)
        # n <= 2: the first two ranks take every draw, and eta's formula
        # divides by zero
        self.eta = ((1.0 - (2.0 / n) ** (1.0 - theta))
                    / (1.0 - zeta2 / self.zetan)) if n > 2 else 0.0
        self.half_pow_theta = 0.5 ** theta

    def ranks(self, rng: np.random.Generator, size: int) -> np.ndarray:
        return self.of_uniform(rng.random(size))

    def of_uniform(self, u: np.ndarray) -> np.ndarray:
        """The ranks that uniform draws ``u`` in [0, 1) map to."""
        uz = u * self.zetan
        out = (self.n * (self.eta * u - self.eta + 1.0) ** self.alpha)
        out = np.minimum(out.astype(np.int64), self.n - 1)
        out = np.where(uz < 1.0 + self.half_pow_theta, 1, out)
        return np.where(uz < 1.0, 0, out)


def scrambled(ranks: np.ndarray, n: int) -> np.ndarray:
    """YCSB's ``ScrambledZipfianGenerator`` step: hash the rank so the hot
    items spread over the keyspace instead of clustering at its start."""
    return np.array([fnvhash64(int(r)) % n for r in ranks], dtype=np.int64)


@dataclass(frozen=True)
class Op:
    due: float                  # seconds after the window opens
    kind: str                   # one of KINDS
    key: bytes                  # b"" for counts and joins
    limit: Optional[int] = None  # range and join pages only
    set_name: bytes = b""       # the set; a join's left-hand set
    right: Optional[bytes] = None     # a join's right-hand set
    join_kind: Optional[str] = None   # intersect, union or difference


@dataclass
class SetData:
    """One set of a configuration: the members set-up inserts, in key-number
    order, and those it then removes."""

    name: bytes
    group: int
    elements: List[bytes]
    removed: List[bytes]


def data(cfg: dict, seed: int) -> Tuple[List[SetData], int]:
    """The sets a configuration's set-up builds, and the first key number
    that no set holds (window inserts count on from there).

    One set (``elements``, ``set_name``): keys 0 to ``elements - 1``.  Many
    (``sets``: ``name_format``, ``universe`` and ``groups`` of ``{count,
    elements}``): each set's members are drawn without replacement from
    the first ``universe`` keys, so sets overlap.  Either way set-up then
    removes a ``remove_share`` of each set's members, drawn from the seed.
    """
    share = float(cfg["remove_share"])

    def drop(elements: List[bytes], rng: np.random.Generator) -> List[bytes]:
        n = len(elements)
        gone = rng.permutation(n)[: int(round(n * share))]
        return [elements[i] for i in sorted(gone.tolist())]

    if "sets" not in cfg:
        n = int(cfg["elements"])
        elements = ycsb_keys(n)
        removed = drop(elements, rng_for(seed, REMOVES))
        return [SetData(cfg["set_name"].encode(), 0, elements, removed)], n
    spec = cfg["sets"]
    universe = int(spec["universe"])
    keys = ycsb_keys(universe)
    rng = rng_for(seed, MEMBERS)
    out: List[SetData] = []
    for group, g in enumerate(spec["groups"]):
        for _ in range(int(g["count"])):
            picked = np.sort(rng.choice(universe, int(g["elements"]),
                                        replace=False))
            elements = [keys[k] for k in picked.tolist()]
            out.append(SetData((spec["name_format"] % len(out)).encode(),
                               group, elements, drop(elements, rng)))
    return out, universe


def arrivals(mix: dict, n_ops: int, seconds: float):
    """Due times, kinds and page lengths (``{"range": [...], "join":
    [...]}``) that every seed of a cell shares."""
    base = rng_for(BASE_SEED, 0)
    gaps = base.exponential(1.0, n_ops)
    gaps *= seconds / gaps.sum()
    due = np.cumsum(gaps) - gaps[0]
    shares: Dict[str, float] = mix["ops"]
    unknown = set(shares) - set(KINDS)
    if unknown:
        raise ValueError(f"unknown op kinds {sorted(unknown)} in mix")
    counts = {k: int(round(shares[k] * n_ops)) for k in shares}
    # rounding leftovers go to the largest share, so the counts sum to n_ops
    biggest = max(shares, key=shares.get)
    counts[biggest] += n_ops - sum(counts.values())
    kinds = [k for k in sorted(counts) for _ in range(counts[k])]
    kinds = [kinds[i] for i in base.permutation(n_ops)]
    lo, hi = mix.get("range_length", (1, 1))
    lengths = {"range": base.integers(lo, hi + 1,
                                      counts.get("range", 0)).tolist()}
    if counts.get("join"):
        lengths["join"] = rng_for(BASE_SEED, 1).integers(
            lo, hi + 1, counts["join"]).tolist()
    return due.tolist(), kinds, lengths


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             sets: Sequence[SetData], first_new_keynum: int) -> List[Op]:
    """Every request of one run's window, in due order.

    A request picks its set by a scrambled Zipfian over ``sets`` (the mix's
    ``set_theta``); a join picks its left-hand set so among the sets of
    the mix's ``join.left`` groups, and its right-hand one, another, among
    those of ``join.right``.  Ranges start and probes land on a scrambled
    Zipfian pick of the set's elements as set-up inserted them; inserts
    add fresh keys from ``first_new_keynum`` on; a remove takes the
    Zipfian pick, or the next element after it that is still live (in the
    next set that has one, where its own has none), so every remove finds
    its element.  A count is the whole set; a join is the first page of
    ``Join(join.kind, left, right)``.
    """
    n_ops = max(1, int(round(rate * seconds)))
    due, kinds, lengths = arrivals(mix, n_ops, seconds)
    lengths = {k: iter(v) for k, v in lengths.items()}
    join = mix.get("join")
    sides = ({side: [i for i, s in enumerate(sets) if s.group in join[side]]
              for side in ("left", "right")} if "join" in kinds else {})
    which = right = np.zeros(n_ops, np.int64)   # indices into ``sets``
    if len(sets) > 1:
        theta = float(mix.get("set_theta", 0.99))
        u = rng_for(seed, SET_PICKS).random(n_ops)
        which = _pick(u, range(len(sets)), theta)
        if sides:
            is_join = np.array([k == "join" for k in kinds])
            which = np.where(is_join, _pick(u, sides["left"], theta), which)
            right = _pick(rng_for(seed, RIGHT_PICKS).random(n_ops),
                          sides["right"], theta)
    sizes = np.array([len(sets[i].elements) for i in which.tolist()])
    u = rng_for(seed, KEYS).random(n_ops)
    ranks = np.zeros(n_ops, np.int64)
    for n in np.unique(sizes).tolist():
        at = sizes == n
        ranks[at] = Zipfian(n, float(mix.get("zipf_theta", 0.99))
                            ).of_uniform(u[at])
    live = [set(s.elements).difference(s.removed) for s in sets]
    keynum = first_new_keynum
    ops: List[Op] = []
    for t, kind, w, r, rank, n in zip(due, kinds, which.tolist(),
                                      right.tolist(), ranks.tolist(),
                                      sizes.tolist()):
        s = sets[w]
        i = fnvhash64(rank) % n
        if kind in ("range", "probe"):
            limit = next(lengths["range"]) if kind == "range" else None
            ops.append(Op(t, kind, s.elements[i], limit, s.name))
        elif kind == "count":
            ops.append(Op(t, kind, b"", None, s.name))
        elif kind == "join":
            if r == w:   # a join of a set with itself: take the next one
                side = sides["right"]
                r = side[(side.index(r) + 1) % len(side)]
            ops.append(Op(t, kind, b"", next(lengths["join"]), s.name,
                          sets[r].name, join["kind"]))
        elif kind == "insert":
            key = ycsb_key(keynum)
            keynum += 1
            live[w].add(key)
            ops.append(Op(t, kind, key, None, s.name))
        else:
            w, key = _live_after(sets, live, w, i)
            live[w].discard(key)
            ops.append(Op(t, kind, key, None, sets[w].name))
    return ops


def _pick(u: np.ndarray, among: Sequence[int], theta: float) -> np.ndarray:
    """A scrambled Zipfian pick of one of ``among`` for each draw of ``u``."""
    among = np.asarray(among, np.int64)
    if not len(among):
        raise ValueError("a join side names no group with a set")
    ranks = Zipfian(len(among), theta).of_uniform(u)
    return among[scrambled(ranks, len(among))]


def _live_after(sets: Sequence[SetData], live: List[set], w: int,
                i: int) -> Tuple[int, bytes]:
    """The first live element at or after element ``i`` of set ``w``, or
    failing that, of the next set that has one."""
    for step in range(len(sets)):
        s = sets[(w + step) % len(sets)]
        n = len(s.elements)
        for k in range(n):
            key = s.elements[(i + k) % n]
            if key in live[(w + step) % len(sets)]:
                return (w + step) % len(sets), key
        i = 0
    raise ValueError("a remove found no live element")
