"""Multi-host pod execution, validated with a REAL 2-process rig.

Two worker processes (4 virtual CPU devices each) initialize
jax.distributed against a shared coordinator, form one global 8-device
mesh, contribute host-local chunk batches, and run the production
pooling program sharded across both processes. Process 0 checks results
against the numpy oracle. This exercises the actual multi-host seams —
coordinator handshake, global mesh, make_array_from_process_local_data —
not a simulation.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

WORKER = textwrap.dedent("""
  import os, sys
  import numpy as np

  os.environ["JAX_PLATFORMS"] = "cpu"
  os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
  ).strip()

  from igneous_tpu.parallel import multihost
  from igneous_tpu.parallel import ChunkExecutor
  from igneous_tpu.ops.oracle import np_downsample_with_averaging

  multihost.initialize()  # env-driven
  import jax
  assert jax.process_count() == 2, jax.process_count()
  assert jax.device_count() == 8, jax.device_count()

  mesh = multihost.pod_mesh()
  pid = jax.process_index()

  # a pod lease of 7 chunks (NOT divisible by 8 devices): lease_partition
  # pads to the canonical size, the last slot is a zero chunk
  N = 7
  rng = np.random.default_rng(0)  # same seed: chunk k is reproducible
  all_chunks = rng.integers(0, 255, (N, 1, 8, 16, 16)).astype(np.uint8)
  mine_idx, per = multihost.lease_partition(N)
  mine = all_chunks[mine_idx]

  ex = ChunkExecutor(mesh, factors=((2, 2, 1),), method="average")
  global_batch = multihost.from_process_local(mesh, mine, per)
  outs, nonzero = ex.run_global(global_batch)
  assert outs[0].shape == (8, 1, 8, 8, 8), outs[0].shape

  # the psum collective crossed processes over the gloo fabric: every
  # process sees the GLOBAL nonzero tally
  assert int(nonzero) == int((all_chunks != 0).sum())

  # each process validates its own addressable shards against the oracle
  # (cross-process shard fetches are not a thing on the CPU backend, just
  # as TPU hosts only address their local chips)
  checked = 0
  for shard in outs[0].addressable_shards:
    k = shard.index[0].start  # global chunk id of this shard
    if k >= N:
      continue  # zero-pad slot
    got = np.asarray(shard.data)[0, 0].transpose(2, 1, 0)
    exp = np_downsample_with_averaging(
      all_chunks[k, 0].transpose(2, 1, 0), (2, 2, 1), 1)[0]
    assert np.array_equal(got, exp), k
    checked += 1
  assert checked >= 3  # this host's share of the 7 real chunks
  print(f"MULTIHOST_OK p{pid}")
""")


def free_port() -> int:
  s = socket.socket()
  s.bind(("127.0.0.1", 0))
  port = s.getsockname()[1]
  s.close()
  return port


def test_two_process_pod_mesh(tmp_path):
  # Failing-since-seed diagnosis (ISSUE 7 satellite): the workers died
  # with "XlaRuntimeError: INVALID_ARGUMENT: Multiprocess computations
  # aren't implemented on the CPU backend" at the first cross-process
  # program. jax defaults `jax_cpu_collectives_implementation` to
  # "none", so the CPU client was built WITHOUT the gloo TCP
  # collectives this jaxlib ships — and the env-var spelling of that
  # config flag is not read by jax 0.4.37, so exporting it in the
  # worker env (the obvious fix) silently did nothing. The real fix
  # lives in multihost.initialize(): a multi-process CPU rig now
  # programmatically switches the CPU client to gloo before backend
  # init. The skip below covers only jaxlib builds that genuinely lack
  # gloo (no make_gloo_tcp_collectives symbol) — there the test cannot
  # pass by construction rather than by misconfiguration.
  from igneous_tpu.parallel import multihost

  if not multihost.cpu_collectives_available():
    pytest.skip(
      "jaxlib built without gloo TCP collectives: multi-process CPU "
      "programs are unimplementable on this build (the seed failure "
      "mode, now config-fixed where gloo exists)"
    )
  port = free_port()
  procs = []
  for pid in range(2):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["IGNEOUS_COORDINATOR"] = f"127.0.0.1:{port}"
    env["IGNEOUS_NUM_PROCESSES"] = "2"
    env["IGNEOUS_PROCESS_ID"] = str(pid)
    env.pop("XLA_FLAGS", None)
    procs.append(subprocess.Popen(
      [sys.executable, "-c", WORKER], env=env,
      cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
      stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    ))
  outs = []
  for p in procs:
    try:
      out, err = p.communicate(timeout=240)
    except subprocess.TimeoutExpired:
      for q in procs:
        q.kill()
      raise
    outs.append((p.returncode, out, err))
  for pid, (rc, out, err) in enumerate(outs):
    assert rc == 0, f"worker {pid} failed rc={rc}:\n{err[-2000:]}"
    assert f"MULTIHOST_OK p{pid}" in out
