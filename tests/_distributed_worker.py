"""Worker program for tests/test_distributed.py — runs in a FRESH process.

Forms a 2-process JAX cluster through runtime.mesh.initialize_distributed
(the jax.distributed.initialize wrapper — the DCN init path SURVEY §2.2's
collectives row requires), builds a GLOBAL mesh spanning both processes'
devices, then executes one cross-process psum and one sharded train step
(fwd + bwd + optimizer update) through the framework's own entry points.

Invoked as: python _distributed_worker.py <process_id> <num_processes> <port>
Prints "WORKER <pid> OK" on success; any assertion/exception exits nonzero.
"""

import os
import sys

# platform must be pinned BEFORE jax initializes a backend: each process
# exposes 2 virtual CPU devices, so the cluster's global mesh has 4
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

pid = int(sys.argv[1])
n_proc = int(sys.argv[2])
port = sys.argv[3]

from k8s_llm_rca_tpu.runtime.mesh import initialize_distributed  # noqa: E402

initialize_distributed(coordinator_address=f"localhost:{port}",
                       num_processes=n_proc, process_id=pid)

import jax  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from k8s_llm_rca_tpu.config import TINY, MeshConfig  # noqa: E402
from k8s_llm_rca_tpu.engine.train import (  # noqa: E402
    init_sharded_train_state, make_train_step, shard_batch,
)
from k8s_llm_rca_tpu.runtime.mesh import build_mesh  # noqa: E402

# the cluster formed: every process sees every process's devices
assert jax.process_count() == n_proc, jax.process_count()
assert jax.process_index() == pid, jax.process_index()
n_global = 2 * n_proc
assert len(jax.devices()) == n_global, jax.devices()
addressable = jax.local_device_count()
assert addressable == 2, addressable

# --- one cross-process psum over the global mesh
mesh = build_mesh(MeshConfig(data=n_proc, model=2))
x = jax.make_array_from_callback(
    (n_global,), NamedSharding(mesh, P(("data", "model"))),
    lambda idx: np.arange(n_global, dtype=np.float32)[idx])
out = jax.jit(jax.shard_map(
    lambda v: jax.lax.psum(v, ("data", "model")), mesh=mesh,
    in_specs=P(("data", "model")), out_specs=P(("data", "model"))))(x)
expected = float(np.arange(n_global).sum())
for shard in out.addressable_shards:
    got = np.asarray(shard.data)
    assert np.allclose(got, expected), (got, expected)
print(f"WORKER {pid} psum={expected}", flush=True)

# --- one sharded train step (fwd + bwd + adamw) across the cluster:
# params TP-sharded over 'model' per llama_param_specs, batch DP-sharded
# over 'data' (which spans the two PROCESSES — gradient psums cross the
# process boundary, the DCN path on a real pod)
cfg = TINY
optimizer = optax.adamw(1e-3)
params, opt_state = init_sharded_train_state(cfg, mesh, optimizer)
tokens = shard_batch(
    np.asarray(jax.random.randint(jax.random.PRNGKey(0), (2 * n_proc, 16),
                                  0, cfg.vocab_size)), mesh)
step = jax.jit(make_train_step(cfg, optimizer))
params, opt_state, loss = step(params, opt_state, tokens)
loss.block_until_ready()
assert np.isfinite(float(loss)), float(loss)
print(f"WORKER {pid} loss={float(loss):.6f}", flush=True)

# --- multi-process SERVING (round-4 review item 5): an ENGINE over the
# process-spanning mesh actually prefills and decodes.  TP weights and
# the KV cache / page pool shard over 'model' and the batch over 'data'
# — BOTH axes span the two processes' devices, so every decode tick's
# collectives cross the process boundary (the DCN serving path).  The
# engine's host driver runs SPMD-identically in each process (same
# prompts, same deterministic schedule), which is exactly how a real
# multi-host serving deployment drives per-host engine replicas of one
# global program.  Greedy tokens must match the single-process plain
# engine (asserted by the test harness against an unsharded reference).
import _distributed_serve_config as serve_cfg  # noqa: E402

from k8s_llm_rca_tpu.engine import make_engine  # noqa: E402
from k8s_llm_rca_tpu.runtime.sharding import (  # noqa: E402
    llama_param_specs, shard_pytree,
)


def _make_sharded(cfg, sparams, stok, secfg):
    return make_engine(
        cfg, secfg, shard_pytree(sparams, llama_param_specs(cfg), mesh),
        stok, tp_mesh=mesh, use_kernel=False)


for key, toks in serve_cfg.serve_all(_make_sharded).items():
    print(f"WORKER {pid} serve[{key}]={toks}", flush=True)
print(f"WORKER {pid} OK", flush=True)
