"""Differential validation of graph/cypher.py (round-3 review+r4: the
interpreter's trail-uniqueness / var-length / direction semantics must be
checked against something that is NOT the interpreter's own expectations).

No Neo4j exists in this image, so the oracle is an INDEPENDENT
brute-force evaluator written from the Cypher spec, sharing nothing with
graph/cypher.py but the store's data model: it enumerates every
relationship-sequence of bounded length by recursion over
``graph.relationships`` adjacency, applying the spec rules directly —

- **trail uniqueness**: a relationship instance appears at most once per
  pattern match (openCypher "relationship isomorphism"; nodes MAY
  repeat),
- **var-length bounds**: ``*lo..hi`` inclusive on both ends,
- **direction**: ``->`` follows start→end, ``<-`` end→start, ``-`` either,
- **type filters** apply per traversed relationship, label filters per
  bound node.

The interpreter takes the same inputs as QUERY TEXT (its real boundary:
parser + planner + matcher), the oracle as structured steps — a bug in
either representation shows up as a multiset mismatch of
(node-id-sequence, rel-id-sequence) paths.  Randomized graphs include
cycles, self-loops, parallel edges and multi-label nodes, the exact
shapes that make trail semantics non-trivial (the reference's ``*1..3``
ladder terminates on cyclic metagraphs only because of rule 1 —
find_metapath/find_srckind_metapath_neo4j.py:96,152-154).
"""

import itertools
import random
from collections import Counter

import pytest

from k8s_llm_rca_tpu.graph.cypher import run_query
from k8s_llm_rca_tpu.graph.store import Graph

# ---------------------------------------------------------------------------
# the independent oracle
# ---------------------------------------------------------------------------


def brute_paths(graph, start_labels, steps, end_labels):
    """Every path matching a linear pattern, by exhaustive enumeration.

    ``steps``: [(direction, type_or_None, lo, hi)] with direction in
    {">", "<", "-"}.  Returns a list of (node_ids, rel_ids) tuples —
    one entry per MATCH row the pattern should produce.
    """

    def has_labels(node, labels):
        return all(lb in node.labels for lb in labels)

    def expansions(node, direction, rel_type):
        """(rel, neighbor) pairs leaving ``node`` along one hop."""
        out = []
        for rel in graph.relationships:
            if rel_type is not None and rel.type != rel_type:
                continue
            if direction in (">", "-") and rel.start_node == node:
                out.append((rel, rel.end_node))
            if direction in ("<", "-") and rel.end_node == node:
                out.append((rel, rel.start_node))
            # an undirected self-loop matches once per orientation,
            # which duplicates the (rel, node) pair — Cypher counts the
            # loop once for `-` patterns, so dedupe that case
        if direction == "-":
            seen, dedup = set(), []
            for rel, nbr in out:
                key = (rel.element_id, nbr.element_id)
                if rel.start_node == rel.end_node and key in seen:
                    continue
                seen.add(key)
                dedup.append((rel, nbr))
            out = dedup
        return out

    results = []

    def advance(step_idx, node, nodes, rels, used):
        if step_idx == len(steps):
            if has_labels(node, end_labels):
                results.append((tuple(n.element_id for n in nodes),
                                tuple(r.element_id for r in rels)))
            return
        direction, rel_type, lo, hi = steps[step_idx]

        def hop(cur, depth, pnodes, prels, pused):
            if lo <= depth:
                advance(step_idx + 1, cur, pnodes, prels, pused)
            if depth == hi:
                return
            for rel, nbr in expansions(cur, direction, rel_type):
                if rel.element_id in pused:          # trail uniqueness
                    continue
                hop(nbr, depth + 1, pnodes + [nbr], prels + [rel],
                    pused | {rel.element_id})

        hop(node, 0, nodes, rels, used)

    for start in graph.nodes:
        if has_labels(start, start_labels):
            advance(0, start, [start], [], frozenset())
    return results


# ---------------------------------------------------------------------------
# query-text construction for the same pattern
# ---------------------------------------------------------------------------


def pattern_query(start_labels, steps, end_labels):
    def label_txt(labels):
        return "".join(f":{lb}" for lb in labels)

    txt = f"(a{label_txt(start_labels)})"
    for i, (direction, rel_type, lo, hi) in enumerate(steps):
        body = f":{rel_type}" if rel_type else ""
        if (lo, hi) != (1, 1):
            body += f"*{lo}..{hi}"
        # empty body exercises the bare `--` parser form
        seg = f"-[{body}]-" if body else "--"
        if direction == ">":
            seg = seg[:-1] + "->"
        elif direction == "<":
            seg = "<" + seg
        mid = (f"(b{label_txt(end_labels)})" if i == len(steps) - 1
               else "()")
        txt += seg + mid
    return f"MATCH p = {txt} RETURN p"


def interp_paths(graph, query):
    rows = run_query(graph, query)
    out = []
    for row in rows:
        p = row["p"]
        out.append((tuple(n.element_id for n in p.nodes),
                    tuple(r.element_id for r in p.relationships)))
    return out


# ---------------------------------------------------------------------------
# randomized graphs
# ---------------------------------------------------------------------------

LABELS = ["Pod", "Node", "Svc", "Pvc"]
TYPES = ["Flow", "Ref", "Has"]


def random_graph(rng):
    g = Graph()
    nodes = []
    for i in range(rng.randint(3, 7)):
        labels = rng.sample(LABELS, rng.randint(1, 2))
        nodes.append(g.add_node(labels, kind=labels[0], idx=i))
    for _ in range(rng.randint(2, 14)):
        a, b = rng.choice(nodes), rng.choice(nodes)   # self-loops allowed
        g.add_relationship(a, rng.choice(TYPES), b)
    return g


def random_pattern(rng):
    start = rng.sample(LABELS, rng.randint(0, 1))
    end = rng.sample(LABELS, rng.randint(0, 1))
    steps = []
    for _ in range(rng.randint(1, 2)):
        direction = rng.choice([">", "<", "-"])
        rel_type = rng.choice([None] + TYPES)
        if rng.random() < 0.6:
            lo = rng.randint(1, 2)
            hi = rng.randint(lo, 3)
        else:
            lo = hi = 1
        steps.append((direction, rel_type, lo, hi))
    return start, steps, end


# ---------------------------------------------------------------------------
# the differential properties
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seed", range(40))
def test_random_patterns_match_brute_force(seed):
    """400 random (graph, pattern) pairs: the interpreter's MATCH rows —
    parsed from query TEXT — equal the spec oracle's enumeration as
    multisets of (node ids, rel ids)."""
    rng = random.Random(1000 + seed)
    for _ in range(10):
        g = random_graph(rng)
        start, steps, end = random_pattern(rng)
        query = pattern_query(start, steps, end)
        got = Counter(interp_paths(g, query))
        want = Counter(brute_paths(g, start, steps, end))
        assert got == want, (query, seed,
                             sorted(got - want), sorted(want - got))


def test_ladder_rung1_directed_varlength_on_adversarial_graphs():
    """Rung 1 of the metapath ladder (`-[*1..3]->`) against the oracle on
    hand-built adversarial graphs: a directed triangle (cycle), a
    diamond with parallel edges, and a self-loop — where naive node- or
    no-uniqueness semantics diverge from trail semantics."""
    # directed triangle + chord
    g = Graph()
    a = g.add_node(["Pod"], kind="Pod")
    b = g.add_node(["Node"], kind="Node")
    c = g.add_node(["Svc"], kind="Svc")
    g.add_relationship(a, "Flow", b)
    g.add_relationship(b, "Flow", c)
    g.add_relationship(c, "Flow", a)           # cycle back
    g.add_relationship(a, "Ref", c)            # chord
    for start, end in itertools.product([["Pod"], []], [["Svc"], []]):
        steps = [(">", None, 1, 3)]
        got = Counter(interp_paths(g, pattern_query(start, steps, end)))
        want = Counter(brute_paths(g, start, steps, end))
        assert got == want, (start, end, got, want)

    # parallel edges: two distinct Flow rels a->b are two distinct trails
    g2 = Graph()
    a2 = g2.add_node(["Pod"], kind="Pod")
    b2 = g2.add_node(["Node"], kind="Node")
    r1 = g2.add_relationship(a2, "Flow", b2)
    r2 = g2.add_relationship(a2, "Flow", b2)
    g2.add_relationship(b2, "Flow", a2)
    steps = [(">", "Flow", 1, 3)]
    got = Counter(interp_paths(g2, pattern_query(["Pod"], steps, ["Pod"])))
    want = Counter(brute_paths(g2, ["Pod"], steps, ["Pod"]))
    assert got == want
    # the a->b->a trails exist via BOTH parallel edges
    assert sum(1 for (ns, rs) in got if len(rs) == 2) >= 2

    # self-loop: one rel, trail-usable once
    g3 = Graph()
    s = g3.add_node(["Pod"], kind="Pod")
    g3.add_relationship(s, "Flow", s)
    for direction in (">", "-"):
        steps = [(direction, None, 1, 3)]
        got = Counter(interp_paths(g3, pattern_query([], steps, [])))
        want = Counter(brute_paths(g3, [], steps, []))
        assert got == want, (direction, got, want)
        assert len(got) == 1                      # exactly one 1-hop trail


def test_ladder_rung2_undirected_varlength_random():
    """Rung 2 (`-[*1..3]-`): undirected var-length on random cyclic
    graphs, where each relationship may be traversed in either
    orientation but still only once per trail."""
    for seed in range(60):
        rng = random.Random(7000 + seed)
        g = random_graph(rng)
        start, _, end = random_pattern(rng)
        steps = [("-", None, 1, 3)]
        got = Counter(interp_paths(g, pattern_query(start, steps, end)))
        want = Counter(brute_paths(g, start, steps, end))
        assert got == want, (seed, sorted(got - want), sorted(want - got))


def test_distinct_endpoints_match_brute_force():
    """The srcKind-walk shape (`RETURN DISTINCT b.kind`): the
    interpreter's DISTINCT projection equals the oracle's de-duplicated
    endpoint kinds."""
    for seed in range(30):
        rng = random.Random(3000 + seed)
        g = random_graph(rng)
        start, steps, end = random_pattern(rng)
        base = pattern_query(start, steps, end)
        query = base.replace("RETURN p", "RETURN DISTINCT b.kind AS k")
        got = sorted(row["k"] for row in run_query(g, query))
        by_end = brute_paths(g, start, steps, end)
        # element ids are assigned interleaved with rels; map via lookup
        id_to_node = {n.element_id: n for n in g.nodes}
        want = sorted({id_to_node[ns[-1]]["kind"] for ns, _ in by_end})
        assert got == want, (seed, query, got, want)


def test_shortest_pruning_inputs_match_brute_force():
    """The ladder's shortest-only pruning consumes len(path) — validate
    the LENGTH DISTRIBUTION of returned paths against the oracle, per
    (start, end) pair, on random graphs (the pruning itself is host
    Python in rca/locator.py; its input contract is what the interpreter
    must get right)."""
    for seed in range(30):
        rng = random.Random(5000 + seed)
        g = random_graph(rng)
        steps = [(">", None, 1, 3)]
        got = interp_paths(g, pattern_query([], steps, []))
        want = brute_paths(g, [], steps, [])

        def dist(paths):
            d = {}
            for ns, rs in paths:
                d.setdefault((ns[0], ns[-1]), Counter())[len(rs)] += 1
            return d

        assert dist(got) == dist(want), seed


# ---------------------------------------------------------------------------
# the ladder's FULL rung-1/rung-2 query text, WHERE clauses included
# ---------------------------------------------------------------------------

# the PRODUCTION rung queries, imported — not retyped — so an edit to
# the locator's WHERE clauses is differentially validated automatically
from k8s_llm_rca_tpu.rca.locator import _Q_DIRECTED, _Q_UNDIRECTED

LADDER = {"->": _Q_DIRECTED.format(hops=3),
          "-": _Q_UNDIRECTED.format(hops=3)}


def brute_ladder(graph, direction, src_kind, dest_kind, inter_kinds):
    """Spec oracle for the FULL rung query: raw var-length trails plus an
    independent re-implementation of every WHERE clause — node
    uniqueness (the all/single quantifier pair), the Event/Namespace
    kind exclusion, endpoint kinds, and the optional intermediate-kind
    disjunction.  Written against the openCypher semantics, not against
    the interpreter's quantifier machinery."""
    out = []
    by_id = {n.element_id: n for n in graph.nodes}
    for node_ids, rel_ids in brute_paths(graph, [],
                                         [(direction, None, 1, 3)], []):
        path_nodes = [by_id[i] for i in node_ids]
        if path_nodes[0]["kind"] != src_kind:
            continue
        if path_nodes[-1]["kind"] != dest_kind:
            continue
        if len(set(node_ids)) != len(node_ids):     # node uniqueness
            continue
        if any(n["kind"] in ("Event", "Namespace") for n in path_nodes):
            continue
        if inter_kinds:
            if not any(n["kind"] in inter_kinds
                       for n in path_nodes[1:-1]):
                continue
        out.append((node_ids, rel_ids))
    return out


LADDER_KINDS = ["Pod", "Node", "Svc", "Pvc", "Event", "Namespace"]


def ladder_graph(rng):
    g = Graph()
    nodes = []
    for i in range(rng.randint(4, 8)):
        kind = rng.choice(LADDER_KINDS)
        nodes.append(g.add_node([kind], kind=kind, idx=i))
    for _ in range(rng.randint(3, 14)):
        a, b = rng.choice(nodes), rng.choice(nodes)
        g.add_relationship(a, rng.choice(TYPES), b)
    return g


@pytest.mark.parametrize("arrow", ["->", "-"])
def test_full_ladder_query_matches_brute_force(arrow):
    """Rungs 1 (directed) and 2 (undirected) of the metapath ladder —
    the exact query TEXT the locator runs, quantifier WHERE clauses and
    all — against the spec oracle on random graphs that include Event /
    Namespace decoys and cycles, across empty / null / non-empty
    $intermediateKinds."""
    direction = ">" if arrow == "->" else "-"
    for seed in range(40):
        rng = random.Random(11000 + seed)
        g = ladder_graph(rng)
        src, dest = rng.choice(LADDER_KINDS[:4]), rng.choice(LADDER_KINDS[:4])
        inter = rng.choice([None, [], ["Node"], ["Node", "Svc"]])
        rows = run_query(g, LADDER[arrow],
                         {"srcKind": src, "destKind": dest,
                          "intermediateKinds": inter})
        got = Counter(
            (tuple(n.element_id for n in row["path"].nodes),
             tuple(r.element_id for r in row["path"].relationships))
            for row in rows)
        want = Counter(brute_ladder(g, direction, src, dest, inter or []))
        assert got == want, (arrow, seed, sorted(got - want),
                             sorted(want - got))
