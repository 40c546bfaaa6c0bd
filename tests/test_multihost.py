"""Multi-host plane with REAL multiple processes (VERDICT r4 item 6).

Two OS processes join one jax.distributed job over the localhost
coordinator (gloo collectives on the CPU backend), each contributing 4
virtual devices to one 8-device global mesh, and run a shard_map program
using the crypto plane's collective pattern (all_gather of per-shard
reductions + psum of counts) through `global_mesh` + `shard_host_batch`.
Anchor: SURVEY §2.3 distributed-comm row; the single-host plane's SPMD
program (parallel/crypto_plane.py) runs over exactly this mesh/sharding
machinery on a multi-host deployment.
"""
import os
import socket
import subprocess
import sys

import pytest

_WORKER = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
jax.config.update("jax_platforms", "cpu")
rank = int(sys.argv[1]); port = sys.argv[2]
from plenum_tpu.parallel.multihost import (init_multihost, global_mesh,
                                           shard_host_batch)
init_multihost(coordinator="127.0.0.1:" + port,
               num_processes=2, process_id=rank)
assert jax.process_count() == 2
import numpy as np
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
mesh = global_mesh()                       # spans both processes' devices
assert mesh.devices.size == 8, mesh.devices.shape

# each "host" stages its local half of a [8, 16] batch (values encode the
# global row index so misplacement is detectable)
local = np.arange(4 * 16, dtype=np.float32).reshape(4, 16) + rank * 64
garr = shard_host_batch(mesh, local, P(("inst", "sig"), None))

shard_map = jax.shard_map

def step(x):
    # the plane's collective pattern: per-shard reduction, all_gather of
    # the partials (every device sees all of them), psum of a count
    part = jnp.sum(x)
    parts = jax.lax.all_gather(part, ("inst", "sig"))
    n = jax.lax.psum(jnp.asarray(1, jnp.int32), ("inst", "sig"))
    return parts, n

f = jax.jit(shard_map(step, mesh=mesh,
                      in_specs=(P(("inst", "sig"), None),),
                      out_specs=(P(None), P()),
                      check_vma=False))
parts, n = f(garr)
want = np.arange(128, dtype=np.float32).reshape(8, 16).sum(axis=1)
assert np.allclose(np.asarray(parts), want), np.asarray(parts)
assert int(n) == 8
print("RANK_OK", rank, flush=True)
"""


def test_two_process_distributed_mesh(tmp_path):
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = str(s.getsockname()[1])
    script = tmp_path / "worker.py"
    script.write_text(_WORKER)
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    procs = [subprocess.Popen(
        [sys.executable, str(script), str(r), port],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
        cwd=str(tmp_path)) for r in range(2)]
    outs = []
    for r, p in enumerate(procs):
        out, _ = p.communicate(timeout=180)
        outs.append(out.decode())
        if "Multiprocess computations aren't implemented" in outs[-1]:
            for q in procs:
                q.kill()
            pytest.skip("this jax build has no cross-process CPU "
                        "collectives (gloo backend missing)")
        assert p.returncode == 0, f"rank{r} failed:\n{outs[-1]}"
    assert "RANK_OK 0" in outs[0]
    assert "RANK_OK 1" in outs[1]
