"""Contract tests for Neo4jQueryExecutor with a mocked bolt driver.

The live-bolt path can't run hermetically (no Neo4j in the image), but its
CONTRACT — mirroring the reference executor (reference
common/neo4j_query_executor.py:6-24) — is testable: connectivity verified
at construction, parameters passed through verbatim, results eagerly
materialized (usable after the session closes), close() delegated to the
driver.  round-1 review item 10.
"""

import sys
import types
from unittest import mock

import pytest


class _FakeResult:
    """Iterable that poisons itself after its session exits, like a real
    bolt result consumed lazily would."""

    def __init__(self, records):
        self._records = records
        self.session_open = True

    def __iter__(self):
        for r in self._records:
            if not self.session_open:
                raise RuntimeError("result consumed after session close")
            yield r


class _FakeSession:
    def __init__(self, records, log):
        self._result = _FakeResult(records)
        self._log = log

    def run(self, query, parameters=None):
        self._log.append(("run", query, parameters))
        return self._result

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self._result.session_open = False
        self._log.append(("session_closed",))
        return False


class _FakeDriver:
    def __init__(self, records):
        self.records = records
        self.log = []
        self.closed = False

    def verify_connectivity(self):
        self.log.append(("verify_connectivity",))

    def session(self):
        return _FakeSession(self.records, self.log)

    def close(self):
        self.closed = True


@pytest.fixture
def fake_neo4j(monkeypatch):
    """Install a fake ``neo4j`` module so the deferred import resolves."""
    driver_box = {}

    def make_driver(uri, auth=None):
        d = _FakeDriver(records=[{"n": 1}, {"n": 2}])
        d.uri, d.auth = uri, auth
        driver_box["driver"] = d
        return d

    mod = types.ModuleType("neo4j")
    mod.GraphDatabase = types.SimpleNamespace(driver=make_driver)
    monkeypatch.setitem(sys.modules, "neo4j", mod)
    return driver_box


def _executor(fake_neo4j):
    from k8s_llm_rca_tpu.graph.executor import Neo4jQueryExecutor

    ex = Neo4jQueryExecutor("bolt://10.1.0.176:7687", "neo4j", "pw")
    return ex, fake_neo4j["driver"]


def test_connectivity_verified_at_construction(fake_neo4j):
    ex, driver = _executor(fake_neo4j)
    assert ("verify_connectivity",) in driver.log
    assert driver.uri == "bolt://10.1.0.176:7687"
    assert driver.auth == ("neo4j", "pw")


def test_parameters_passed_through_verbatim(fake_neo4j):
    ex, driver = _executor(fake_neo4j)
    params = {"message": 'quoted "msg" with $dollar', "limit": 5}
    ex.run_query("MATCH (n) WHERE n.m CONTAINS $message RETURN n", params)
    run_calls = [c for c in driver.log if c[0] == "run"]
    assert run_calls == [("run",
                          "MATCH (n) WHERE n.m CONTAINS $message RETURN n",
                          params)]
    # None parameters forward as None (driver treats it as no params)
    ex.run_query("MATCH (n) RETURN n")
    assert driver.log[-2] == ("run", "MATCH (n) RETURN n", None)


def test_results_eagerly_materialized(fake_neo4j):
    """list(session.run(...)) must happen INSIDE the session context: the
    reference's callers iterate records long after the query returns
    (reference test_all.py:133-135)."""
    ex, driver = _executor(fake_neo4j)
    records = ex.run_query("MATCH (n) RETURN n")
    # session is closed by now; a lazy result would raise on iteration
    assert [r["n"] for r in records] == [1, 2]
    assert driver.log[-1] == ("session_closed",)


def test_close_delegates_to_driver(fake_neo4j):
    ex, driver = _executor(fake_neo4j)
    ex.close()
    assert driver.closed


def test_in_memory_executor_same_protocol(fake_neo4j):
    """Both executors satisfy GraphQueryExecutor: run_query(query, params)
    -> eager list, close() -> None.  The pipeline treats them uniformly."""
    from k8s_llm_rca_tpu.graph import InMemoryGraphExecutor
    from k8s_llm_rca_tpu.graph.fixtures import build_metagraph

    bolt, _ = _executor(fake_neo4j)
    mem = InMemoryGraphExecutor(build_metagraph())
    for ex in (bolt, mem):
        out = ex.run_query("MATCH (n1) WHERE n1.category IN "
                           "['NativeEntity', 'ExternalEntity'] "
                           "RETURN n1.category AS category, n1.kind AS kind")
        assert isinstance(out, list)
        assert ex.close() is None
