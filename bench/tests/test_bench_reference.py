"""The plain reference against hand-checked cases and the program's
oracle engine, and the control against the reference."""
import numpy as np
import pytest

from bench.configs.linear_xpath import Reference, decode, parse
from bench.tests.tiny import CONFIG
from bench.traffic import generator

NAMES = ["a", "b", "c"]


def doc(*events):
    """``("a", "b", "/b", "/a")`` -> (kind, tag) arrays."""
    kinds = [1 if e.startswith("/") else 0 for e in events]
    tags = [NAMES.index(e.lstrip("/")) for e in events]
    return np.asarray(kinds, np.int8), np.asarray(tags)


@pytest.mark.parametrize("profile,events,want", [
    ("/a", ("a", "/a"), True),
    ("/b", ("a", "b", "/b", "/a"), False),
    ("//b", ("a", "b", "/b", "/a"), True),
    ("/a/b", ("a", "c", "b", "/b", "/c", "/a"), False),
    ("/a//b", ("a", "c", "b", "/b", "/c", "/a"), True),
    ("//a/*/b", ("a", "c", "b", "/b", "/c", "/a"), True),
    ("//a//a", ("a", "/a", "a", "/a"), False),
    ("//a//a", ("a", "a", "/a", "/a"), True),
    ("//c/b", ("a", "/a", "c", "/c", "b", "/b"), False),
    ("/c", ("a", "/a", "c", "/c"), True),
])
def test_hand_checked(profile, events, want):
    got = Reference([profile], NAMES).match(*doc(*events))
    assert (got.size == 1) == want


def test_child_relaxed_control_differs():
    ref = Reference(["/a/b"], NAMES)
    ctl = Reference(["/a/b"], NAMES, relax_child=True)
    d = doc("a", "c", "b", "/b", "/c", "/a")
    assert ref.match(*d).size == 0 and ctl.match(*d).size == 1


def test_parse_rejects_non_linear_paths():
    assert parse("//t1/*") == [(True, "t1"), (False, "*")]
    with pytest.raises(ValueError):
        parse("//t1[t2]")


def test_agrees_with_the_programs_oracle():
    from repro.core import engines
    from repro.core.dictionary import TagDictionary
    from repro.core.events import EventBatch, EventStream
    from repro.core.nfa import compile_queries
    from repro.core.xpath import parse as xparse

    dep = generator.deployment(CONFIG)
    profiles = dep.profiles + ["/site", "/site//item", "//person/*",
                               "/*/*/*", "//text//emph", "//listitem/text"]
    d = TagDictionary()
    for n in dep.names:
        d.add(n)
    nfa = compile_queries([xparse(p) for p in profiles], d, shared=True)
    oracle = engines.create("oracle", nfa, dictionary=d)
    ref = Reference(profiles, dep.names)
    pool = generator.Pool.build(dep, CONFIG["documents"], 21)
    s = generator.Stream(pool, 21)
    for _ in range(len(pool)):
        p = s.next()
        k, t = decode(p)
        res = oracle.filter_batch(EventBatch.from_streams(
            [EventStream(k, t.astype(np.int32))]))
        assert np.array_equal(ref.match(k, t),
                              np.flatnonzero(res.matched[0]))
