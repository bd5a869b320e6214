"""Serving-tier smoke (ISSUE 9 CI step).

Boots `igneous serve` as a real subprocess over a seeded file:// layer,
then asserts the acceptance criteria end to end:

  * a 16-client thundering herd on ONE cold chunk coalesces into
    exactly 1 backend fetch (serve.fetch == 1, serve.requests == 16 in
    the journaled counters);
  * served bytes are identical to direct storage reads, both in the
    compressed domain (Accept-Encoding: gzip -> stored wire bytes
    verbatim) and transcoded (no Accept-Encoding -> CloudFiles.get);
  * per-tier cache counters and per-request serve.request spans land in
    the journal (igneous fleet trace can render a request);
  * SIGTERM drains gracefully — an idle keep-alive connection does not
    wedge the drain and the process exits 0.

Usage: python tools/serve_smoke.py [--size 64]
"""

import argparse
import gzip
import http.client
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

HERD = 16


def serve_env():
  env = dict(os.environ)
  env.update({
    "JAX_PLATFORMS": "cpu",
    "PYTHONUNBUFFERED": "1",
  })
  return env


def get(port, path, headers=None):
  conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
  try:
    conn.request("GET", path, headers=headers or {})
    resp = conn.getresponse()
    return resp.status, dict(resp.getheaders()), resp.read()
  finally:
    conn.close()


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--size", type=int, default=64)
  args = ap.parse_args()

  tmp = tempfile.mkdtemp(prefix="igneous-serve-smoke-")
  path = f"file://{tmp}/layer"
  jpath = f"file://{tmp}/journal"

  from igneous_tpu.storage import CloudFiles
  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(9)
  n = args.size
  data = rng.integers(0, 255, (n, n, n)).astype(np.uint8)
  Volume.from_numpy(data, path, chunk_size=(n, n, n))  # gzip-stored
  chunk = f"1_1_1/0-{n}_0-{n}_0-{n}"
  cf = CloudFiles(path)
  stored, method = cf.get_stored(chunk)
  assert method == "gzip", f"seed layer should be gzip-stored, got {method}"

  proc = subprocess.Popen(
    [sys.executable, "-m", "igneous_tpu", "serve", path,
     "--port", "0", "--host", "127.0.0.1", "--journal", jpath,
     "--no-synth"],
    env=serve_env(), cwd=REPO, stdout=subprocess.PIPE,
    stderr=subprocess.STDOUT, text=True,
  )
  try:
    port = None
    deadline = time.time() + 120
    for line in proc.stdout:
      sys.stdout.write(line)
      if line.startswith("{"):
        try:
          rec = json.loads(line)
        except ValueError:
          continue
        if rec.get("event") == "serve.listening":
          port = rec["port"]
          break
      if time.time() > deadline:
        break
    assert port, "serve never printed its listening line"

    # thundering herd FIRST (server fully cold): 16 concurrent clients,
    # one chunk — the coalescer must make exactly one origin fetch
    barrier = threading.Barrier(HERD)
    bodies = [None] * HERD

    def hammer(i):
      barrier.wait()
      _, _, bodies[i] = get(port, f"/{chunk}", {"Accept-Encoding": "gzip"})

    threads = [
      threading.Thread(target=hammer, args=(i,)) for i in range(HERD)
    ]
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    assert all(b == stored for b in bodies), (
      "herd responses differ from the stored wire bytes"
    )
    print(f"herd: {HERD} clients, all byte-identical to storage")

    # byte identity, transcoded path (client accepts no gzip)
    status, headers, body = get(port, f"/{chunk}")
    assert status == 200 and "Content-Encoding" not in headers
    assert body == cf.get(chunk), "transcoded body != CloudFiles.get"
    assert gzip.decompress(stored) == body

    # warm hit off the RAM tier
    status, headers, _ = get(port, f"/{chunk}", {"Accept-Encoding": "gzip"})
    assert headers.get("X-Igneous-Cache") == "ram", headers.get(
      "X-Igneous-Cache"
    )

    # SIGTERM drain: an idle keep-alive connection must not wedge it
    idle = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    idle.request("GET", "/healthz")
    idle.getresponse().read()  # keep-alive: connection stays open, idle
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=60)
    idle.close()
    assert rc == 0, f"serve exited {rc} on SIGTERM (want clean drain = 0)"
    print("SIGTERM drain: exit 0 with an idle keep-alive connection open")
  finally:
    if proc.poll() is None:
      proc.kill()
      proc.wait(timeout=30)

  from igneous_tpu.observability import fleet
  from igneous_tpu.observability import journal as journal_mod

  records = list(journal_mod.read_records(jpath))
  assert records, "serve left no journal segments"
  counters = {}
  for rec in records:
    if rec.get("kind") == "counters":
      counters.update(rec.get("counters") or {})
  assert counters.get("serve.fetch") == 1, (
    f"herd of {HERD} must cost exactly 1 backend fetch, "
    f"saw {counters.get('serve.fetch')}"
  )
  assert counters.get("serve.requests", 0) >= HERD + 2
  leaders = counters.get("serve.coalesce.leaders", 0)
  waiters = counters.get("serve.coalesce.waiters", 0)
  ram_hits = counters.get("serve.cache.ram.hits", 0)
  assert leaders == 1, f"exactly one coalition leader expected, got {leaders}"
  assert waiters + ram_hits >= HERD - 1, (
    f"non-leader herd clients must ride the single flight or the RAM "
    f"tier: waiters={waiters} ram_hits={ram_hits}"
  )
  print(f"counters: fetch=1 leaders=1 waiters={waiters} ram_hits={ram_hits}")

  spans = [r for r in records if r.get("kind") == "span"]
  reqs = [s for s in spans if s.get("name") == "serve.request"]
  assert len(reqs) >= HERD, f"per-request spans missing ({len(reqs)})"
  sample = next(s for s in reqs if s.get("tier") == "origin")
  tree = fleet.trace_records(records, sample["trace"])
  assert any(s["name"] == "serve.fetch" for s in tree), (
    "origin request trace lacks its serve.fetch child span"
  )
  rendered = fleet.render_trace(tree)
  assert rendered
  print("\n".join(rendered))
  print("serve smoke OK")


if __name__ == "__main__":
  main()
