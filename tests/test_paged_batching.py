"""Ragged paged device batching (ISSUE 12).

The contract under test: a ragged fleet of cutouts rides ONE compiled
signature per kernel per campaign (pages + extent sidecars, filler pages
zero), and the reassembled outputs are bitwise-identical to the solo
paths. Plus the pod-mesh seam: page ranges shard across a REAL 2-process
mesh via ``page_partition`` + ``PagedGlobalRunner``.
"""

import os
import socket
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from igneous_tpu.observability import device as device_mod
from igneous_tpu.ops import edt as edt_mod
from igneous_tpu.ops import pooling
from igneous_tpu.ops.ccl import connected_components
from igneous_tpu.parallel import multihost, paged


@pytest.fixture(autouse=True)
def _clean_ledger():
  device_mod.reset()
  yield
  device_mod.reset()


def _sig_count(kernel: str) -> int:
  return sum(1 for k, _ in device_mod.LEDGER._signatures if k == kernel)


# nothing page-aligned: edges on every axis, plus a degenerate voxel
RAGGED_SHAPES = [(64, 64, 32), (33, 64, 17), (7, 5, 3), (64, 33, 64),
                 (1, 1, 1)]


# ---------------------------------------------------------------------------
# bitwise identity vs the solo paths


@pytest.mark.parametrize("dtype,method,factor,num_mips,sparse", [
  (np.uint8, "average", (2, 2, 1), 2, False),
  (np.uint64, "mode", (2, 2, 2), 1, True),
  (np.uint32, "mode", (2, 2, 1), 2, False),
])
def test_paged_pyramid_bitwise_vs_solo(
  rng, dtype, method, factor, num_mips, sparse
):
  imgs = [
    rng.integers(0, 200, s).astype(dtype) for s in RAGGED_SHAPES
  ]
  if np.dtype(dtype).itemsize == 8:
    for img in imgs:  # exercise the (lo, hi) uint64 plane split
      img[img == 3] = np.uint64(2**40 + 7)
  got = paged.paged_pyramid(
    imgs, factor, num_mips, method=method, sparse=sparse
  )
  for img, mips in zip(imgs, got):
    exp = pooling.downsample(
      img, factor, num_mips, method=method, sparse=sparse
    )
    assert len(mips) == len(exp)
    for e, g in zip(exp, mips):
      assert g.dtype == e.dtype
      assert np.array_equal(g, e), img.shape


def test_paged_pyramid_channels_bitwise(rng):
  imgs = [
    rng.integers(0, 255, s + (3,)).astype(np.uint8)
    for s in [(33, 18, 9), (64, 64, 32), (5, 5, 5)]
  ]
  got = paged.paged_pyramid(imgs, (2, 2, 1), 2, method="average")
  for img, mips in zip(imgs, got):
    exp = pooling.downsample(img, (2, 2, 1), 2, method="average")
    for e, g in zip(exp, mips):
      assert np.array_equal(g, e)


def test_paged_pyramid_single_signature_per_campaign(rng, monkeypatch):
  # unique page geometry so this campaign's signature is fresh in this
  # process regardless of what other tests compiled
  monkeypatch.setenv("IGNEOUS_PAGE_SHAPE", "16,16,16")
  monkeypatch.setenv("IGNEOUS_PAGE_BATCH", "8")
  imgs = [
    rng.integers(0, 255, s).astype(np.uint8) for s in RAGGED_SHAPES * 2
  ]
  p = paged.PagedPyramid(imgs, (2, 2, 1), 2, method="average")
  assert p.rounds_remaining > 1  # multiple rounds, still one signature
  p.run()
  assert _sig_count("pooling.paged_pyramid[average]") == 1
  snap = device_mod.LEDGER.snapshot()
  assert snap["pad_bytes"] > 0
  assert 0.0 < snap["pad_waste_ratio"] < 1.0


def test_paged_ccl_bitwise_vs_solo(rng, monkeypatch):
  monkeypatch.setenv("IGNEOUS_CCL_BACKEND", "device")
  labs = [
    ((rng.random(s) < 0.55) * rng.integers(1, 4, s)).astype(np.uint32)
    for s in [(40, 33, 21), (17, 3, 9), (64, 64, 32), (1, 1, 5)]
  ]
  got = paged.paged_ccl(labs, 6)
  for lab, g in zip(labs, got):
    exp = connected_components(lab, 6)
    assert np.array_equal(g, exp), lab.shape
  assert _sig_count("ccl.paged[scan]") + _sig_count("ccl.paged[relax]") <= 1


def test_paged_edt_bitwise_vs_solo(rng, monkeypatch):
  monkeypatch.setenv("IGNEOUS_EDT_BACKEND", "device")
  anis = (1.8, 1.0, 2.5)
  labs = [
    ((rng.random(s) < 0.6) * rng.integers(1, 3, s)).astype(np.uint32)
    for s in [(19, 13, 7), (40, 9, 21), (3, 3, 3)]
  ]
  got = paged.paged_edt(labs, anis)
  for lab, g in zip(labs, got):
    exp = edt_mod.edt(lab, anis, black_border=True)
    assert g.dtype == np.float32
    assert np.array_equal(g, exp), lab.shape
  # one canonical shape per fleet → one signature for the whole campaign
  assert _sig_count("edt.sq_paged") <= 1


# ---------------------------------------------------------------------------
# knobs + page table mechanics


def test_page_knobs(monkeypatch):
  assert paged.pages_compatible(((2, 2, 1), (2, 2, 2)))
  assert not paged.pages_compatible(((3, 3, 3),))
  assert not paged.pages_compatible(((2, 2, 1),) * 6)  # cum 64 > 32
  assert paged.ccl_page_compatible()  # default tile divides default page
  monkeypatch.setenv("IGNEOUS_PAGE_SHAPE", "64,32,32")
  assert paged.page_shape() == (64, 32, 32)
  assert paged.pages_compatible(((1, 1, 2),) * 6)  # z cum 64 divides 64
  monkeypatch.setenv("IGNEOUS_PAGE_SHAPE", "0,32,32")
  with pytest.raises(ValueError):
    paged.page_shape()
  monkeypatch.delenv("IGNEOUS_PAGE_SHAPE")
  monkeypatch.setenv("IGNEOUS_PAGE_BATCH", "5")
  import jax

  cap = paged.page_round_cap(jax.device_count())
  assert cap >= 5
  assert cap % jax.device_count() == 0
  assert cap & (cap - 1) == 0  # pow2


def test_incompatible_chain_refused(rng):
  with pytest.raises(ValueError, match="pages_compatible"):
    paged.PagedPyramid(
      [rng.integers(0, 9, (9, 9, 9)).astype(np.uint8)], (3, 3, 3), 1,
    )


def test_split_unstarted_sheds_only_untouched_items(rng, monkeypatch):
  monkeypatch.setenv("IGNEOUS_PAGE_SHAPE", "4,4,4")
  monkeypatch.setenv("IGNEOUS_PAGE_BATCH", "1")
  imgs = [
    rng.integers(0, 255, (4, 4, 4)).astype(np.uint8),   # 1 page
    rng.integers(0, 255, (8, 4, 4)).astype(np.uint8),   # 2 pages
    rng.integers(0, 255, (4, 8, 8)).astype(np.uint8),   # 4 pages
  ]
  p = paged.PagedPyramid(imgs, (2, 2, 2), 1, method="average")
  first_page = [0, 1, 3]  # item-contiguous page table
  p.run_round()
  dispatched = min(p.cap, 7)
  shed = p.split_unstarted()
  assert shed == [i for i in range(3) if first_page[i] >= dispatched]
  while p.pending:
    p.run_round()
  for i in range(3):
    if i in shed:
      with pytest.raises(ValueError, match="not complete"):
        p.result(i)
    else:
      exp = pooling.downsample(imgs[i], (2, 2, 2), 1, method="average")
      got = p.result(i)
      assert np.array_equal(got[0], exp[0])


def test_page_partition_single_process():
  import jax

  start, stop, per = multihost.page_partition(10)
  assert (start, stop) == (0, 10)
  assert per >= 10 - start
  assert per % max(jax.device_count() // jax.process_count(), 1) == 0
  with pytest.raises(ValueError, match="weights"):
    multihost.page_partition(10, weights=[1.0, 2.0, 3.0][: 2])


# ---------------------------------------------------------------------------
# 2-process pod mesh: page ranges shard across hosts


WORKER = textwrap.dedent("""
  import os, sys
  import numpy as np

  os.environ["JAX_PLATFORMS"] = "cpu"
  os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=4"
  ).strip()

  from igneous_tpu.parallel import multihost
  from igneous_tpu.parallel.paged import PagedGlobalRunner
  from igneous_tpu.ops.oracle import np_downsample_with_averaging

  multihost.initialize()  # env-driven
  import jax
  assert jax.process_count() == 2, jax.process_count()
  assert jax.device_count() == 8, jax.device_count()

  mesh = multihost.pod_mesh()
  pid = jax.process_index()

  # a ragged fleet cut into 8^3 pages: 1 + 2 + 4 = 7 pages (NOT divisible
  # by 8 devices); every process rebuilds the same page table from seed 0
  rng = np.random.default_rng(0)
  shapes = [(8, 8, 8), (16, 8, 8), (16, 16, 8)]  # (z, y, x), page-aligned
  items = [rng.integers(0, 255, s).astype(np.uint8) for s in shapes]
  pages = []
  for it in items:
    Z, Y, X = it.shape
    for oz in range(0, Z, 8):
      for oy in range(0, Y, 8):
        for ox in range(0, X, 8):
          pages.append(it[None, oz:oz+8, oy:oy+8, ox:ox+8])  # (c=1, ...)
  pages = np.stack(pages)
  exts = np.full((len(pages), 3), 8, np.int32)
  N = pages.shape[0]
  assert N == 7

  start, stop, per = multihost.page_partition(N)
  gp = multihost.from_process_local(mesh, pages[start:stop], per)
  ge = multihost.from_process_local(mesh, exts[start:stop], per)

  runner = PagedGlobalRunner(((2, 2, 1),), method="average", mesh=mesh)
  outs = runner(gp, ge)
  out0 = outs[0]
  assert out0.shape == (per * 2, 1, 8, 4, 4), out0.shape

  # each process validates its own addressable page shards against the
  # numpy oracle (hosts only address their local chips, as on TPU pods)
  checked = 0
  for shard in out0.addressable_shards:
    k = shard.index[0].start  # global page id of this shard
    if k >= N:
      continue  # zero-pad slot
    got = np.asarray(shard.data)[0, 0].transpose(2, 1, 0)  # zyx -> xyz
    exp = np_downsample_with_averaging(
      pages[k, 0].transpose(2, 1, 0), (2, 2, 1), 1)[0]
    assert np.array_equal(got, exp), k
    checked += 1
  assert checked >= 3  # this host's share of the 7 real pages
  print(f"PAGED_POD_OK p{pid}")
""")


def free_port() -> int:
  s = socket.socket()
  s.bind(("127.0.0.1", 0))
  port = s.getsockname()[1]
  s.close()
  return port


def test_two_process_paged_pod_mesh(tmp_path):
  if not multihost.cpu_collectives_available():
    pytest.skip(
      "jaxlib built without gloo TCP collectives: multi-process CPU "
      "programs are unimplementable on this build"
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
    assert f"PAGED_POD_OK p{pid}" in out
