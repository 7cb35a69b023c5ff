"""Multi-process distributed init: the DCN path, actually executed.

SURVEY §2.2's collectives row and §5's distributed-backend row call for
``jax.distributed.initialize``-based multi-host init (the reference has no
distributed anything — its whole comm story is HTTPS + two bolt sockets,
reference common/neo4j_query_executor.py:3-8).  Everything else multi-chip
in this suite runs on ONE process with virtual devices; these tests spawn
TWO separate processes that form a real cluster through
``runtime.mesh.initialize_distributed`` (coordinator + worker over a local
TCP port), build a global mesh spanning both processes' devices, and run
one cross-process psum and one sharded train step (tests/_distributed_worker.py).
"""

import os
import socket
import subprocess
import sys

import pytest

_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "_distributed_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def _spawn(pid: int, n_proc: int, port: int) -> subprocess.Popen:
    # each process of the cluster gets 2 virtual CPU devices
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    return subprocess.Popen(
        [sys.executable, _WORKER, str(pid), str(n_proc), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env)


def test_two_process_cluster_psum_train_and_serve():
    """Coordinator (process 0) + worker (process 1) form a cluster via
    initialize_distributed; each asserts the global device view, runs a
    cross-process psum, a DP×TP train step whose gradient reductions
    cross the process boundary, and then SERVES: the engine (at two
    page sizes) prefills and decodes over the process-spanning TP mesh, every
    tick's collectives crossing the process boundary.  Both processes
    must exit 0 with matching losses, matching served tokens, and the
    served tokens must equal a SINGLE-process unsharded engine's greedy
    output (computed here) — the DCN serving claim, executed."""
    port = _free_port()
    procs = [_spawn(i, 2, port) for i in range(2)]
    outs = []
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out)
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-3000:]}"
        assert f"WORKER {i} OK" in out, out[-3000:]
    # the jitted train step is one program over one global mesh: both
    # processes must report the IDENTICAL loss
    losses = [line.split("loss=")[1].split()[0]
              for out in outs for line in out.splitlines()
              if "loss=" in line]
    assert len(losses) == 2 and losses[0] == losses[1], losses

    # serving parity: both processes emitted identical tokens per leg
    def serve_lines(out):
        return {line.split("serve[")[1].split("]=")[0]:
                line.split("]=")[1].strip()
                for line in out.splitlines() if "serve[" in line}

    served = [serve_lines(o) for o in outs]
    assert set(served[0]) == {"page8/batch", "page8/single",
                              "page16/batch", "page16/single"}, served[0]
    assert served[0] == served[1], (served[0], served[1])

    # ... and match the single-process unsharded engines exactly — the
    # scenario definition is SHARED with the worker
    # (tests/_distributed_serve_config.py), so both sides serve the same
    # prompts/configs by construction
    from k8s_llm_rca_tpu.engine import make_engine

    import _distributed_serve_config as serve_cfg

    def _make_plain(cfg, params, tok, ecfg):
        return make_engine(cfg, ecfg, params, tok, use_kernel=False)

    want = serve_cfg.serve_all(_make_plain)
    assert served[0] == want, (served[0], want)
