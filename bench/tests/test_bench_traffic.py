"""The benchmark's copy of the traffic: seeded, salted, faithful."""
import numpy as np
import pytest

from bench.configs import linear_xpath
from bench.traffic import arrivals, generator
from bench.tests.tiny import CONFIG

BIG_SEED = 2**33 + 12345


def stream(seed, n):
    pool = generator.Pool.build(generator.deployment(CONFIG),
                                CONFIG["documents"], seed)
    s = generator.Stream(pool, seed)
    return pool, s, [s.next() for _ in range(n)]


def test_same_seed_gives_same_pool_and_stream():
    p1, s1, a = stream(BIG_SEED, 20)
    p2, s2, b = stream(BIG_SEED, 20)
    assert all(np.array_equal(x, y) for x, y in zip(p1.templates,
                                                    p2.templates))
    assert a == b and s1.trees == s2.trees
    p3, _, c = stream(BIG_SEED + 1, 20)
    assert a != c
    # seeds change the trees, not the entity counts the scale fixes
    dtd = generator.deployment(CONFIG).dtd
    for name in ("item", "person", "open_auction", "closed_auction"):
        t = dtd.names.index(name)
        n1 = {int(((k == 0) & (g == t)).sum())
              for k, g in zip(p1.kinds, p1.tags)}
        n3 = {int(((k == 0) & (g == t)).sum())
              for k, g in zip(p3.kinds, p3.tags)}
        assert len(n1) == 1 and n1 == n3


@pytest.mark.parametrize("mix", [
    {"arrivals": {"kind": "poisson", "rate_hz": 37.0}},
    {"arrivals": {"kind": "burst", "rate_hz": 400.0, "on_s": 0.05,
                  "off_s": 0.15}}])
def test_same_seed_gives_same_schedule(mix):
    a = arrivals.schedule(mix, 20.0, generator.rng_for(BIG_SEED, "arr"))
    b = arrivals.schedule(mix, 20.0, generator.rng_for(BIG_SEED, "arr"))
    c = arrivals.schedule(mix, 20.0, generator.rng_for(7, "arr"))
    assert np.array_equal(a, b) and not np.array_equal(a, c)
    # the same number of requests in the window, in another order
    assert a.size == c.size
    assert np.all(np.diff(a) > 0)


def test_poisson_seeds_share_their_gaps():
    mix = {"arrivals": {"kind": "poisson", "rate_hz": 37.0}}
    a = arrivals.schedule(mix, 20.0, generator.rng_for(BIG_SEED, "arr"))
    c = arrivals.schedule(mix, 20.0, generator.rng_for(7, "arr"))
    assert np.allclose(np.sort(np.diff(a, prepend=0)),
                       np.sort(np.diff(c, prepend=0)))


def test_poisson_schedule_fills_the_window():
    rate, secs = 64.0, 20.0
    due = arrivals.poisson(rate, secs, generator.rng_for(3, "arr"))
    assert due.size == round(rate * secs)
    assert abs(due[-1] - secs) < 5 / rate


def test_burst_arrivals_fall_in_on_windows():
    due = arrivals.burst(400.0, 10.0, generator.rng_for(3, "arr"),
                         on_s=0.05, off_s=0.15)
    assert due.size == round(400.0 * 10.0 * 0.25)
    assert np.all(np.mod(due, 0.2) < 0.05 + 1e-9)


def test_salted_payloads_are_unique_and_decode_to_the_tree():
    from repro.core.events import _sym_table, decode_bytes, validate_payload

    pool, s, payloads = stream(11, 3 * 8)
    assert len(set(payloads)) == len(payloads)
    for p, tree in zip(payloads, s.trees):
        ev = decode_bytes(p, _sym_table())
        assert np.array_equal(ev.kind, pool.kinds[tree])
        assert np.array_equal(ev.tag_id, pool.tags[tree])
        kinds, tags = linear_xpath.decode(p)
        assert np.array_equal(kinds, pool.kinds[tree])
        assert np.array_equal(tags, pool.tags[tree])
        validate_payload(p)
        assert len(p) == len(pool.templates[tree])


def test_deployment_matches_the_programs_generator():
    from repro.data.generator import DTD, gen_profiles

    dep = generator.deployment(CONFIG)
    dtd = DTD(len(dep.names), dep.dtd.children, dep.names)
    p = CONFIG["profiles"]
    want = [q.raw for n, length in zip(p["per_length"], p["lengths"])
            for q in gen_profiles(dtd, n=n, length=length, p_desc=0.3,
                                  p_wild=0.1, seed=length)]
    assert dep.profiles == want


@pytest.mark.parametrize("model,want", [
    ("EMPTY", ("seq", [], "")),
    ("(#PCDATA)", ("seq", [("pcdata",)], "")),
    ("(a, b?, c*)", ("seq", [("name", "a", ""), ("name", "b", "?"),
                             ("name", "c", "*")], "")),
    ("(#PCDATA | a | b)*", ("alt", [("pcdata",), ("name", "a", ""),
                                    ("name", "b", "")], "*")),
    ("(a, (b | c)+, d)", ("seq", [("name", "a", ""),
                                  ("alt", [("name", "b", ""),
                                           ("name", "c", "")], "+"),
                                  ("name", "d", "")], "")),
])
def test_parse_model(model, want):
    assert generator.parse_model(model) == want


@pytest.mark.parametrize("model", ["(a, b | c)", "(a, b", "(a) x", "a b"])
def test_parse_model_rejects(model):
    with pytest.raises((ValueError, IndexError)):
        generator.parse_model(model)


def _model_regex(p, names) -> str:
    """A content model as a regex over ``<name>`` tokens."""
    if p[0] == "pcdata":
        return ""
    if p[0] == "name":
        body = f"<{names[p[1]]}>"
    elif p[0] == "seq":
        body = "".join(_model_regex(q, names) for q in p[1])
    else:
        body = "|".join(_model_regex(q, names) for q in p[1])
    return f"(?:{body}){p[2]}"


def test_trees_are_valid_against_the_dtd():
    """Every element below the depth cut holds a child sequence its
    content model allows; the entity counts are the scaled ones."""
    import re

    dep = generator.deployment(CONFIG)
    dtd, docs = dep.dtd, dict(CONFIG["documents"], pool=16)
    pool = generator.Pool.build(dep, docs, 5)
    pats = [re.compile(_model_regex(m, dtd.names)) for m in dtd.models]
    for kinds, tags in zip(pool.kinds, pool.tags):
        assert tags[0] == dtd.root and kinds[0] == generator.OPEN
        stack: list[list[int]] = [[]]
        depth_of: list[int] = []
        for k, t in zip(kinds, tags):
            if k == generator.OPEN:
                stack[-1].append(int(t))
                stack.append([])
                depth_of.append(len(stack) - 1)
                continue
            kids = stack.pop()
            depth = depth_of.pop()
            if depth < docs["max_depth"]:
                seq = "".join(f"<{dtd.names[c]}>" for c in kids)
                assert pats[t].fullmatch(seq), (dtd.names[t], seq)
        n_item = int(((kinds == 0) & (tags == dtd.names.index("item"))).sum())
        assert n_item == 2          # europe 0.6 and namerica 1.0 round to 1


def test_text_bytes_follow_text_elements():
    dep = generator.deployment(CONFIG)
    names = dep.names
    kinds = np.array([0, 0, 1, 0, 1, 1], np.int8)
    tags = np.array([names.index(n) for n in
                     ("site", "regions", "regions", "name", "name",
                      "site")])
    text = np.where(dep.dtd.text, 3, 0)
    buf, pos = generator.encode(kinds, tags, text)
    # <site><regions></regions><name>xxx</name></site>
    assert len(buf) == 4 + 4 + 5 + 4 + 3 + 5 + 5
    assert pos.tolist() == [17, 18, 19]
    assert bytes(buf[pos]) == b"xxx"
    k, t = linear_xpath.decode(buf.tobytes())
    assert np.array_equal(k, kinds) and np.array_equal(t, tags)
