"""The benchmark's own workload generator.

A configuration fixes a deployment: a DTD given as its content models
(``dtd.elements``), and a PathGenerator-style profile set drawn from it
with fixed seeds; :func:`gen_profiles` repeats the draws of
``repro.data.generator.gen_profiles``.  The documents are random trees
valid against the DTD at the configuration's scale (:class:`TreeGen`),
encoded in the paper's wire format: fixed two-symbol tags, ``<ss>`` then
the element's text bytes, ``</ss>``.

A run draws its stream from a pool of distinct trees made from the seed.
Every submission re-salts the text bytes with seeded letters, so no two
payloads of a run are byte-identical while the structure, and so the
work, stays the tree's.
"""
from __future__ import annotations

import re
from dataclasses import dataclass

import numpy as np

ALPHABET = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_.",
    np.uint8)
LT, GT, SLASH = ord("<"), ord(">"), ord("/")
OPEN, CLOSE = 0, 1
OPEN_NBYTES, CLOSE_NBYTES = 4, 5
#: salt letters: filler text is ``[a-z]``, never a ``<`` marker
SALT_LO, SALT_HI = ord("a"), ord("z") + 1


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose); any whole seed."""
    words = [int(seed) & 0xFFFFFFFF, (int(seed) >> 32) & 0xFFFFFFFF,
             *stream.encode()]
    return np.random.default_rng(np.random.SeedSequence(words))


# ------------------------------------------------------------- deployment
#: one particle of a content model: ``("name", tag, occ)``,
#: ``("seq" | "alt", [particles], occ)`` or ``("pcdata",)``
_TOKEN = re.compile(r"\s*(#PCDATA|[A-Za-z_][-A-Za-z0-9_.]*|[(),|?*+])")


def parse_model(model: str):
    """A DTD content model (``"(a, (b | c)*, d?)"``, ``"(#PCDATA)"``,
    ``"EMPTY"``) as nested particles; ``EMPTY`` is an empty sequence."""
    if model.strip() == "EMPTY":
        return ("seq", [], "")
    toks = _TOKEN.findall(model)
    if "".join(toks) != re.sub(r"\s+", "", model):
        raise ValueError(f"not a content model: {model!r}")
    pos = 0

    def occ():
        nonlocal pos
        if pos < len(toks) and toks[pos] in "?*+":
            pos += 1
            return toks[pos - 1]
        return ""

    def particle():
        nonlocal pos
        tok = toks[pos]
        pos += 1
        if tok == "#PCDATA":
            return ("pcdata",)
        if tok != "(":
            return ("name", tok, occ())
        items, sep = [particle()], None
        while toks[pos] != ")":
            if sep not in (None, toks[pos]):
                raise ValueError(f"mixed , and | in one group: {model!r}")
            sep = toks[pos]
            pos += 1
            items.append(particle())
        pos += 1
        return ("alt" if sep == "|" else "seq", items, occ())

    out = particle()
    if pos != len(toks):
        raise ValueError(f"trailing tokens in {model!r}")
    return out


def _ids(p, ids: dict[str, int]):
    """The particle with element names replaced by tag ids."""
    if p[0] == "name":
        if p[1] not in ids:
            raise ValueError(f"element {p[1]!r} is not declared")
        return ("name", ids[p[1]], p[2])
    if p[0] == "pcdata":
        return p
    return (p[0], [_ids(q, ids) for q in p[1]], p[2])


def _names_in(p) -> list[int]:
    if p[0] == "name":
        return [p[1]]
    if p[0] == "pcdata":
        return []
    return [n for q in p[1] for n in _names_in(q)]


def _has_pcdata(p) -> bool:
    return p[0] == "pcdata" or (p[0] in ("seq", "alt")
                                and any(_has_pcdata(q) for q in p[1]))


@dataclass
class Dtd:
    """A document type: element names (tag id = index), the root, each
    element's parsed content model, the child tags it allows, and whether
    it holds text."""

    names: list[str]
    root: int
    models: list
    children: dict[int, list[int]]
    text: np.ndarray

    @classmethod
    def from_config(cls, d: dict) -> "Dtd":
        names = list(d["elements"])
        ids = {n: i for i, n in enumerate(names)}
        models = [_ids(parse_model(d["elements"][n]), ids) for n in names]
        children = {-1: [ids[d["root"]]]}
        for i, m in enumerate(models):
            kids = dict.fromkeys(_names_in(m))
            children[i] = list(kids)
        text = np.array([_has_pcdata(m) for m in models])
        return cls(names, ids[d["root"]], models, children, text)


def gen_profiles(children: dict[int, list[int]], names: list[str], *,
                 n: int, length: int, p_desc: float, p_wild: float,
                 seed: int) -> list[str]:
    """PathGenerator-style linear profiles: random root-to-descendant
    walks through the DTD; the same draws as
    ``repro.data.generator.gen_profiles`` over the same child lists."""
    rng = np.random.default_rng(seed)
    out: list[str] = []
    for _ in range(n):
        tags: list[int] = []
        cur = -1
        for _ in range(length):
            opts = children.get(cur, [])
            if not opts:
                break
            cur = int(rng.choice(opts))
            tags.append(cur)
        parts = []
        for i, t in enumerate(tags):
            axis = "//" if (i == 0 or rng.random() < p_desc) else "/"
            name = "*" if rng.random() < p_wild else names[t]
            parts.append(axis + name)
        out.append("".join(parts))
    return out


@dataclass
class Deployment:
    """What a configuration file fixes: schema and subscription set."""

    dtd: Dtd
    profiles: list[str]
    scale: float

    @property
    def names(self) -> list[str]:
        return self.dtd.names


def deployment(cfg: dict) -> Deployment:
    dtd = Dtd.from_config(cfg["dtd"])
    p = cfg["profiles"]
    profiles: list[str] = []
    for length, n in zip(p["lengths"], p["per_length"]):
        profiles += gen_profiles(dtd.children, dtd.names, n=n, length=length,
                                 p_desc=p["p_desc"], p_wild=p["p_wild"],
                                 seed=p["seed_base"] + length)
    return Deployment(dtd, profiles, float(cfg["scale_factor"]))


# -------------------------------------------------------------- documents
class TreeGen:
    """Random documents valid against a :class:`Dtd`, one root element
    each.  A repeated particle named in ``docs["counts"]``
    (``"parent/child"`` -> count at scale factor 1) repeats
    ``round(count * scale)`` times, at least once where it is ``+``; any
    other ``*`` repeats 0 to ``star_max`` times, ``+`` 1 to
    ``star_max``, ``?`` with probability 1/2, and a choice takes each
    branch alike (a ``#PCDATA`` branch adds no element).  An element at
    ``max_depth`` gets no children."""

    def __init__(self, dtd: Dtd, docs: dict, scale: float):
        self.dtd = dtd
        self.max_depth = int(docs["max_depth"])
        self.star_max = int(docs["star_max"])
        ids = {n: i for i, n in enumerate(dtd.names)}
        self.fixed: dict[tuple[int, int], int] = {}
        for key, count in docs["counts"].items():
            parent, child = key.split("/")
            if ids[child] not in dtd.children[ids[parent]]:
                raise ValueError(f"{key}: {child} is no child of {parent}")
            self.fixed[ids[parent], ids[child]] = int(
                np.floor(count * scale + 0.5))

    def tree(self, rng: np.random.Generator) -> tuple[np.ndarray, np.ndarray]:
        kinds: list[int] = []
        tags: list[int] = []

        def element(tag: int, depth: int) -> None:
            kinds.append(OPEN)
            tags.append(tag)
            if depth < self.max_depth:
                for kid in expand(tag, self.dtd.models[tag]):
                    element(kid, depth + 1)
            kinds.append(CLOSE)
            tags.append(tag)

        def times(parent: int, p) -> int:
            o = p[-1]
            if p[0] == "name" and (parent, p[1]) in self.fixed:
                n = self.fixed[parent, p[1]]
                return max(n, 1) if o == "+" else n
            if o == "*":
                return int(rng.integers(0, self.star_max + 1))
            if o == "+":
                return int(rng.integers(1, self.star_max + 1))
            if o == "?":
                return int(rng.random() < 0.5)
            return 1

        def expand(parent: int, p) -> list[int]:
            if p[0] == "pcdata":
                return []
            out: list[int] = []
            for _ in range(times(parent, p)):
                if p[0] == "name":
                    out.append(p[1])
                elif p[0] == "seq":
                    for q in p[1]:
                        out += expand(parent, q)
                else:
                    out += expand(parent, p[1][int(rng.integers(len(p[1])))])
            return out

        element(self.dtd.root, 1)
        return np.asarray(kinds, np.int8), np.asarray(tags, np.int32)


def encode(kinds: np.ndarray, tags: np.ndarray, text: np.ndarray
           ) -> tuple[np.ndarray, np.ndarray]:
    """Events -> (wire bytes as uint8, positions of the text bytes);
    ``text[i]`` bytes follow the open tag of an element with tag ``i``."""
    is_open = kinds == OPEN
    n_text = np.where(is_open, text[tags], 0)
    size = np.where(is_open, OPEN_NBYTES, CLOSE_NBYTES) + n_text
    off = np.concatenate([[0], np.cumsum(size)[:-1]]).astype(np.int64)
    out = np.empty(int(size.sum()), np.uint8)
    s0, s1 = ALPHABET[tags >> 6], ALPHABET[tags & 63]
    o, c = off[is_open], off[~is_open]
    out[o], out[o + 1], out[o + 2], out[o + 3] = (
        LT, s0[is_open], s1[is_open], GT)
    starts = np.repeat(o + OPEN_NBYTES, n_text[is_open])
    first = np.repeat(np.cumsum(n_text[is_open]) - n_text[is_open],
                      n_text[is_open])
    pos = starts + np.arange(starts.size) - first
    out[pos] = ord("x")
    out[c], out[c + 1], out[c + 2], out[c + 3], out[c + 4] = (
        LT, SLASH, s0[~is_open], s1[~is_open], GT)
    return out, pos


@dataclass
class Pool:
    """Distinct document trees and their encoded templates."""

    kinds: list[np.ndarray]
    tags: list[np.ndarray]
    templates: list[np.ndarray]
    text_pos: list[np.ndarray]

    @classmethod
    def build(cls, dep: Deployment, docs: dict, seed: int) -> "Pool":
        rng = rng_for(seed, "pool")
        gen = TreeGen(dep.dtd, docs, dep.scale)
        text = np.where(dep.dtd.text, int(docs["text_bytes"]), 0)
        kinds, tags, tmpl, pos = [], [], [], []
        for _ in range(docs["pool"]):
            k, t = gen.tree(rng)
            b, p = encode(k, t, text)
            kinds.append(k)
            tags.append(t)
            tmpl.append(b)
            pos.append(p)
        return cls(kinds, tags, tmpl, pos)

    def __len__(self) -> int:
        return len(self.templates)

    @property
    def max_bytes(self) -> int:
        return max(len(t) for t in self.templates)

    def salted(self, i: int, rng: np.random.Generator) -> bytes:
        """Tree ``i``'s payload with freshly drawn text letters."""
        buf = self.templates[i].copy()
        pos = self.text_pos[i]
        buf[pos] = rng.integers(SALT_LO, SALT_HI, size=pos.size,
                                dtype=np.uint8)
        return buf.tobytes()


class Stream:
    """The seeded sequence of submissions: tree ids cycle through the
    pool in a fresh seeded order each pass, and every payload is salted.
    Deterministic in ``seed`` and the submission index alone."""

    def __init__(self, pool: Pool, seed: int):
        self.pool = pool
        self._order = rng_for(seed, "order")
        self._salt = rng_for(seed, "salt")
        self._perm: list[int] = []
        self.trees: list[int] = []

    def next(self) -> bytes:
        if not self._perm:
            self._perm = self._order.permutation(len(self.pool)).tolist()
        i = self._perm.pop()
        self.trees.append(i)
        return self.pool.salted(i, self._salt)
