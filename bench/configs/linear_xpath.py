"""Plain reference for linear XPath profile filtering.

The semantics, written out without any of the program's code: a profile
``a1 s1 a2 s2 ... ak sk`` (axes ``/`` child, ``//`` descendant; tests a
tag name or ``*``) matches a document when some element ``v`` ends a
chain ``v1, ..., vk = v`` in which ``vi`` passes test ``si``, ``v1`` is a
top-level element (leading ``/``) or any element (leading ``//``), and
each ``vi`` is a child (``/``) or a proper descendant (``//``) of
``v(i-1)``.  A document may hold several top-level elements.

Whether step ``i`` can end at ``v`` depends only on the tags along the
root-to-``v`` path, so the evaluation runs once per distinct path,
level by level, over every step of every distinct profile at once:

    M[v, i] = test_i(tag v) and (i = 1: leading // or depth(v) = 1;
                                  i > 1, child: M[parent v, i-1];
                                  i > 1, descendant: A[parent v, i-1])
    A[v, i] = A[parent v, i] or M[v, i]       (some ancestor-or-self)

and a profile matches when ``M[v, last]`` holds for some ``v``.

``relax_child=True`` evaluates every ``/`` as ``//``: the control, which
breaks the configuration's exactness guarantee the way a kernel that
skipped the parent test would.
"""
from __future__ import annotations

import re

import numpy as np

_ALPHABET = (b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ"
             b"0123456789_.")
_SYM = np.full(256, -1, np.int64)
_SYM[np.frombuffer(_ALPHABET, np.uint8)] = np.arange(64)
_STEP = re.compile(r"(//|/)([A-Za-z_][-A-Za-z0-9_.]*|\*)")
OPEN, CLOSE = 0, 1


def decode(payload: bytes) -> tuple[np.ndarray, np.ndarray]:
    """Wire bytes -> (kind, tag id) events.  ``<`` + two symbols opens,
    ``</`` + two symbols closes; anything else is text."""
    b = np.frombuffer(payload, np.uint8)
    n = b.size
    pad = np.concatenate([b, np.zeros(4, np.uint8)])
    lt = np.flatnonzero(b == ord("<"))
    close = pad[lt + 1] == ord("/")
    s = lt + 1 + close
    v0, v1 = _SYM[pad[s]], _SYM[pad[s + 1]]
    if (v0 < 0).any() or (v1 < 0).any() or (s + 2 > n).any():
        raise ValueError("undecodable tag marker")
    return close.astype(np.int8), (v0 << 6 | v1).astype(np.int64)


def parse(profile: str) -> list[tuple[bool, str]]:
    """``"//t3/t5//*"`` -> ``[(True, "t3"), (False, "t5"), (True, "*")]``."""
    steps = [(m.group(1) == "//", m.group(2))
             for m in _STEP.finditer(profile)]
    if "".join(a + t for a, t in ((("//" if d else "/"), t)
                                  for d, t in steps)) != profile:
        raise ValueError(f"not a linear path profile: {profile!r}")
    return steps


def _pack(bits: np.ndarray) -> np.ndarray:
    """Bool array (..., n) -> uint64 words (..., ceil(n / 64))."""
    n = bits.shape[-1]
    pad = np.zeros(bits.shape[:-1] + (-n % 64,), bool)
    b = np.packbits(np.concatenate([bits, pad], axis=-1), axis=-1,
                    bitorder="little")
    return b.view(np.uint64) if b.flags.c_contiguous else \
        np.ascontiguousarray(b).view(np.uint64)


def _unpack(words: np.ndarray, n: int) -> np.ndarray:
    return np.unpackbits(words.view(np.uint8), bitorder="little")[:n] \
        .astype(bool)


def _shift(x: np.ndarray) -> np.ndarray:
    """Every step's bit moved one step up (step p - 1 -> step p)."""
    out = x << np.uint64(1)
    out[:, 1:] |= x[:, :-1] >> np.uint64(63)
    return out


class Reference:
    """Exact match lists of a profile set over documents.

    ``profiles[i]`` is profile id ``i``; ``tag_names[t]`` is the element
    name carried on the wire as tag id ``t``.
    """

    def __init__(self, profiles: list[str], tag_names: list[str], *,
                 relax_child: bool = False):
        distinct: dict[str, list[int]] = {}
        for i, p in enumerate(profiles):
            distinct.setdefault(p, []).append(i)
        self._ids = [np.asarray(v, np.int32) for v in distinct.values()]
        tag_of = {n: t for t, n in enumerate(tag_names)}
        desc, first, tests, last = [], [], [], []
        for p in distinct:
            steps = parse(p)
            for i, (d, name) in enumerate(steps):
                desc.append(d or (relax_child and i > 0))
                first.append(i == 0)
                tests.append(-1 if name == "*" else tag_of.get(name, -2))
            last.append(len(desc) - 1)
        # steps as bits: step p is bit p % 64 of word p // 64, and step
        # p - 1 of the same profile is the bit below it
        desc = np.asarray(desc)
        first_at = np.zeros(desc.size, bool)
        first_at[np.flatnonzero(first)] = True
        self._desc = _pack(desc)
        self._first = _pack(first_at)
        self._first_desc = _pack(first_at & desc)
        self._last = np.asarray(last)
        tests = np.asarray(tests)
        # row t: the steps tag t passes; the last row serves tags outside
        # the schema, which only '*' passes
        n = len(tag_names)
        test = np.zeros((n + 1, desc.size), bool)
        test[tests[tests >= 0], np.flatnonzero(tests >= 0)] = True
        test[:, tests == -1] = True
        self._test = _pack(test)
        self._n_steps = desc.size
        self._n_tags = n

    def _paths(self, kinds: np.ndarray, tags: np.ndarray):
        """Distinct root-to-element paths as a trie: (parent, tag, depth)
        per node; node 0 is the document above the top level."""
        parent, tag, depth = [-1], [-1], [0]
        index: dict[tuple[int, int], int] = {}
        stack = [0]
        for k, t in zip(kinds.tolist(), tags.tolist()):
            if k == CLOSE:
                if len(stack) == 1:
                    raise ValueError("close tag without an open element")
                stack.pop()
                continue
            p = stack[-1]
            v = index.get((p, t))
            if v is None:
                v = index[(p, t)] = len(tag)
                parent.append(p)
                tag.append(min(t, self._n_tags))
                depth.append(depth[p] + 1)
            stack.append(v)
        if len(stack) != 1:
            raise ValueError("unclosed elements")
        return (np.asarray(parent), np.asarray(tag), np.asarray(depth))

    def match(self, kinds: np.ndarray, tags: np.ndarray) -> np.ndarray:
        """Sorted ids of the profiles that match one document."""
        parent, tag, depth = self._paths(kinds, tags)
        words = self._test.shape[1]
        hit = np.zeros(words, np.uint64)
        row = np.zeros(parent.size, np.int64)   # node -> row in its level
        m_prev = a_prev = np.zeros((1, words), np.uint64)
        for d in range(1, int(depth.max(initial=0)) + 1):
            nodes = np.flatnonzero(depth == d)
            row[nodes] = np.arange(nodes.size)
            up = row[parent[nodes]]
            a_up = a_prev[up]
            src = (_shift(a_up) & self._desc) | (_shift(m_prev[up])
                                                 & ~self._desc)
            start = self._first if d == 1 else self._first_desc
            src = (src & ~self._first) | start
            m = src & self._test[tag[nodes]]
            a_prev = a_up | m
            m_prev = m
            hit |= np.bitwise_or.reduce(m, axis=0)
        hit = _unpack(hit, self._n_steps)[self._last]
        if not hit.any():
            return np.zeros(0, np.int32)
        return np.sort(np.concatenate(
            [self._ids[i] for i in np.flatnonzero(hit)]))

    def match_payload(self, payload: bytes) -> np.ndarray:
        return self.match(*decode(payload))
