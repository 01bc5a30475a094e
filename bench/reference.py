"""The plain reference: a Python set per set of the deployment, fed the
same operations in the same order, and the comparison that decides a
run's ``correct``.

It shares no code with the program under test.  Elements order as bytes,
which is the order the program's key codec keeps for elements without a
NUL byte (YCSB keys have none).
"""
from __future__ import annotations

import bisect
import heapq
import itertools
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Mapping, Optional, Sequence, Tuple

# every comparison is exact: its limit is 0 wrong answers
LIMITS = {
    "wrong_pages": 0,       # range and join pages
    "wrong_probes": 0,
    "wrong_counts": 0,      # runs that sent a count only
    "wrong_removes": 0,
    "lost_writes": 0,
    "scan_mismatch": 0,
    "lost_on_restart": 0,   # durable configurations only
}


class ReferenceSet:
    """A sorted list and a set over the same elements."""

    def __init__(self, elements: Iterable[bytes] = ()):
        self._set = set(elements)
        self._sorted = sorted(self._set)

    def __contains__(self, element: bytes) -> bool:
        return element in self._set

    def __len__(self) -> int:
        return len(self._set)

    def add(self, element: bytes) -> None:
        if element not in self._set:
            self._set.add(element)
            bisect.insort(self._sorted, element)

    def discard(self, element: bytes) -> bool:
        if element not in self._set:
            return False
        self._set.remove(element)
        del self._sorted[bisect.bisect_left(self._sorted, element)]
        return True

    def page(self, start: bytes, limit: int) -> List[bytes]:
        """The first ``limit`` members at or after ``start``."""
        i = bisect.bisect_left(self._sorted, start)
        return self._sorted[i:i + limit]

    def members(self) -> List[bytes]:
        return list(self._sorted)


def join_page(kind: str, left: ReferenceSet, right: ReferenceSet,
              limit: int) -> List[bytes]:
    """The first ``limit`` members, in byte order, of the intersect, union
    or difference (left less right) of two sets."""
    if kind == "intersect":
        small, big = sorted((left, right), key=len)
        found: Iterable[bytes] = (el for el in small._sorted if el in big)
    elif kind == "union":
        found = (el for el, _ in itertools.groupby(
            heapq.merge(left._sorted, right._sorted)))
    elif kind == "difference":
        found = (el for el in left._sorted if el not in right)
    else:
        raise ValueError(f"unknown join kind {kind!r}")
    return list(itertools.islice(found, limit))


@dataclass
class Answer:
    """What the program replied to one request of the window."""

    kind: str
    key: bytes
    limit: Optional[int] = None
    set_name: bytes = b""               # a join's left-hand set
    right: Optional[bytes] = None       # a join's right-hand set
    join_kind: Optional[str] = None
    ok: bool = False                    # False: refused or never served
    members: Optional[List[bytes]] = None  # range and join pages
    present: Optional[bool] = None         # probes
    removed: Optional[bool] = None         # removes
    count: Optional[int] = None            # counts


@dataclass
class Verdict:
    numbers: Dict[str, int] = field(default_factory=dict)
    examples: List[str] = field(default_factory=list)

    @property
    def correct(self) -> bool:
        return all(n <= LIMITS[k] for k, n in self.numbers.items())

    def note(self, what: str, example: str) -> None:
        self.numbers[what] += 1
        if len(self.examples) < 5:
            self.examples.append(f"{what}: {example}")


Key = Tuple[bytes, bytes]   # (set, element)


def judge(initial: Mapping[bytes, Iterable[bytes]],
          answers: Sequence[Answer],
          final_scan: Mapping[bytes, Sequence[bytes]],
          replicas: Mapping[Key, List[bool]],
          restarted: Optional[Mapping[Key, List[bool]]] = None) -> Verdict:
    """Replay ``answers`` against the reference, in order, then compare the
    read-back with the reference's final state.

    ``initial`` holds each set's members when the window opens.  Every
    served answer is compared.  A refused or unserved request is not an
    answer: it did nothing on the program's side and does nothing here.
    A range page must be the set's first ``limit`` members from its
    start, a join page the first ``limit`` of the exact intersect, union
    or difference, a count the set's size (``wrong_counts`` is reported
    only where the run sent a count).  ``final_scan`` is each set read
    back whole at the configuration's quorum; ``replicas`` gives, for each
    (set, element) an acknowledged write touched, its presence on each
    replica the configuration writes to.  ``lost_writes`` counts each
    acknowledged insert missing, and each acknowledged remove present, in
    its set's quorum read-back or on any of those replicas;
    ``scan_mismatch`` counts every element on which a set's quorum
    read-back and the reference disagree.

    ``restarted`` (durable configurations) gives, for every (set, element)
    of the sets' whole key space the run wrote, its presence on each
    replica after all of them lost power and recovered from their own
    media alone; ``lost_on_restart`` counts each (set, element, replica)
    on which that disagrees with the reference.
    """
    refs = {name: ReferenceSet(members) for name, members in initial.items()}
    reported = set(LIMITS)
    if restarted is None:
        reported.discard("lost_on_restart")
    if not any(a.kind == "count" for a in answers):
        reported.discard("wrong_counts")
    v = Verdict(numbers={k: 0 for k in LIMITS if k in reported})
    inserted: List[Key] = []
    removed: List[Key] = []
    for a in answers:
        if not a.ok:
            continue
        ref = refs[a.set_name]
        if a.kind == "range":
            want = ref.page(a.key, a.limit)
            if a.members != want:
                v.note("wrong_pages",
                       f"range {a.set_name!r} {a.key!r} limit {a.limit}: "
                       f"got {len(a.members)} members, want {len(want)}")
        elif a.kind == "join":
            want = join_page(a.join_kind, ref, refs[a.right], a.limit)
            if a.members != want:
                v.note("wrong_pages",
                       f"join {a.join_kind} {a.set_name!r} {a.right!r} "
                       f"limit {a.limit}: got {len(a.members)} members, "
                       f"want {len(want)}")
        elif a.kind == "probe":
            if a.present != (a.key in ref):
                v.note("wrong_probes",
                       f"probe {a.set_name!r} {a.key!r}: got {a.present}")
        elif a.kind == "count":
            if a.count != len(ref):
                v.note("wrong_counts",
                       f"count {a.set_name!r}: got {a.count}, "
                       f"want {len(ref)}")
        elif a.kind == "insert":
            ref.add(a.key)
            inserted.append((a.set_name, a.key))
        elif a.kind == "remove":
            was = ref.discard(a.key)
            if a.removed != was:
                v.note("wrong_removes",
                       f"remove {a.set_name!r} {a.key!r}: got {a.removed}, "
                       f"want {was}")
            if a.removed:
                removed.append((a.set_name, a.key))
        else:
            raise ValueError(f"unknown answer kind {a.kind!r}")
    scans = {name: set(final_scan.get(name, ())) for name in refs}
    for s, key in inserted:
        if key not in refs[s]:
            continue
        if key not in scans[s]:
            v.note("lost_writes",
                   f"acknowledged insert {s!r} {key!r} not read back")
        for i, there in enumerate(replicas.get((s, key), [False])):
            if not there:
                v.note("lost_writes", f"acknowledged insert {s!r} {key!r} "
                       f"missing on replica {i}")
    for s, key in removed:
        if key in scans[s]:
            v.note("lost_writes",
                   f"acknowledged remove {s!r} {key!r} read back")
        for i, there in enumerate(replicas.get((s, key), [])):
            if there:
                v.note("lost_writes", f"acknowledged remove {s!r} {key!r} "
                       f"still on replica {i}")
    if restarted is not None:
        for (s, key), there in restarted.items():
            for i, present in enumerate(there):
                if present != (key in refs[s]):
                    v.note("lost_on_restart",
                           f"{s!r} {key!r} "
                           f"{'present' if present else 'missing'} "
                           f"on replica {i} after restart")
    for name, ref in refs.items():
        got, want = list(final_scan.get(name, ())), ref.members()
        if got != want:
            diff = len(scans[name].symmetric_difference(want)) or 1  # order
            v.numbers["scan_mismatch"] += diff
            v.examples.append(
                f"scan_mismatch: {name!r} read back {len(got)} elements, "
                f"reference holds {len(want)}")
    return v
