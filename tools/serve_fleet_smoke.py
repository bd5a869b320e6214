"""Serve-federation smoke (ISSUE 18 CI step).

Boots THREE real `igneous serve` replicas (subprocesses, auto-assigned
ports, shared file:// membership directory) over one seeded layer, then
proves the federation's headline economics end to end:

  * a seeded zipfian herd — the stationary request mix of a synthetic
    million-user viewer population — spread across all replicas costs
    EXACTLY one origin fetch per distinct chunk, fleet-wide
    (counter-asserted from the shared journal);
  * served bytes and ETags are identical on every replica, peer-filled
    or origin-filled;
  * the auto-assigned ports (serve + metrics) land machine-parsable in
    the `serve.listening` line, and the metrics port exposes the
    `igneous_serve_fleet_*` gauges;
  * SIGTERM-draining one replica leaves the fleet serving, including
    chunks the dead replica owned (graceful leave + origin fallback);
  * under forced overload (tiny `IGNEOUS_SERVE_QOS_RPS`) the fleet
    sheds with 503 + Retry-After instead of melting.

Writes the headline numbers to fleet-report.json (--report-out).

Usage: python tools/serve_fleet_smoke.py [--requests 600] [--clients 12]
"""

import argparse
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

REPLICAS = 3


def serve_env(**extra):
  env = dict(os.environ)
  env.update({
    "JAX_PLATFORMS": "cpu",
    "PYTHONUNBUFFERED": "1",
    # fast ring convergence + counters visible without waiting for drain
    "IGNEOUS_SERVE_FLEET_TTL_SEC": "3",
    "IGNEOUS_JOURNAL_FLUSH_SEC": "1",
  })
  env.update(extra)
  return env


def get(port, path, headers=None):
  conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
  try:
    conn.request("GET", path, headers=headers or {})
    resp = conn.getresponse()
    return resp.status, dict(resp.getheaders()), resp.read()
  finally:
    conn.close()


def boot_replica(layer_path, jpath, members, extra_env=None):
  proc = subprocess.Popen(
    [sys.executable, "-m", "igneous_tpu", "serve", layer_path,
     "--port", "0", "--metrics-port", "0", "--host", "127.0.0.1",
     "--journal", jpath, "--no-synth"]
    + (["--peers-file", members] if members else []),
    env=serve_env(**(extra_env or {})), cwd=REPO, stdout=subprocess.PIPE,
    stderr=subprocess.STDOUT, text=True,
  )
  deadline = time.time() + 120
  listening = None
  for line in proc.stdout:
    sys.stdout.write(line)
    if line.startswith("{"):
      try:
        rec = json.loads(line)
      except ValueError:
        continue
      if rec.get("event") == "serve.listening":
        listening = rec
        break
    if time.time() > deadline:
      break
  assert listening, "replica never printed its serve.listening line"
  # satellite: --port 0 / --metrics-port 0 auto-assignment must land
  # every BOUND port in the machine-parsable readiness line
  assert listening["port"], listening
  assert listening["metrics_port"], listening
  # drain the rest of stdout on a reaper thread so the pipe never fills
  t = threading.Thread(
    target=lambda: [sys.stdout.write(ln) for ln in proc.stdout], daemon=True
  )
  t.start()
  return proc, listening


def aggregate_counters(jpath):
  """Latest counters snapshot per worker, summed across the fleet
  (each replica journals cumulative counters under its own worker id)."""
  from igneous_tpu.observability import journal as journal_mod

  latest = {}
  for rec in journal_mod.read_records(jpath):
    if rec.get("kind") != "counters":
      continue
    worker = rec.get("worker", "?")
    prev = latest.get(worker)
    if prev is None or rec.get("ts", 0) >= prev.get("ts", 0):
      latest[worker] = rec
  totals = {}
  for rec in latest.values():
    for k, v in (rec.get("counters") or {}).items():
      totals[k] = totals.get(k, 0) + v
  return totals


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--size", type=int, default=128, help="volume edge (vox)")
  ap.add_argument("--requests", type=int, default=600)
  ap.add_argument("--clients", type=int, default=12)
  ap.add_argument("--users", type=int, default=1_000_000,
                  help="synthetic viewer population behind the zipf mix")
  ap.add_argument("--seed", type=int, default=9)
  ap.add_argument("--report-out", default="fleet-report.json")
  args = ap.parse_args()

  tmp = tempfile.mkdtemp(prefix="igneous-fleet-smoke-")
  layer_path = f"file://{tmp}/layer"
  jpath = f"file://{tmp}/journal"
  members = f"file://{tmp}/members"

  from igneous_tpu.serve import HashRing, strong_etag
  from igneous_tpu.storage import CloudFiles

  from igneous_tpu.volume import Volume

  rng = np.random.default_rng(args.seed)
  n = args.size
  data = rng.integers(0, 255, (n, n, n)).astype(np.uint8)
  Volume.from_numpy(data, layer_path, chunk_size=(32, 32, 32))
  cf = CloudFiles(layer_path)
  chunks = sorted(k for k in cf.list() if k.startswith("1_1_1/"))
  assert len(chunks) >= 32, f"seed produced only {len(chunks)} chunks"
  # hold some chunks out of the herd so the drain phase can request
  # provably-cold keys owned by the dead replica
  herd_pool, reserved = chunks[:-8], chunks[-8:]

  report = {"requests": args.requests, "clients": args.clients,
            "users": args.users, "chunks": len(chunks)}
  procs = []
  try:
    infos = []
    for i in range(REPLICAS):
      proc, info = boot_replica(layer_path, jpath, members)
      procs.append(proc)
      infos.append(info)
    ports = [info["port"] for info in infos]
    urls = [info["self_url"] for info in infos]
    layer_name = "layer"

    # ring convergence: every replica must see all three members
    deadline = time.time() + 60
    while time.time() < deadline:
      rings = []
      for port in ports:
        _, _, body = get(port, "/-/fed/status")
        rings.append(json.loads(body)["ring"])
      if all(sorted(r) == sorted(urls) for r in rings):
        break
      time.sleep(0.25)
    else:
      raise AssertionError(f"ring never converged: {rings} != {urls}")
    print(f"ring converged: {len(urls)} replicas")

    # ---- phase 1: the zipfian million-user herd --------------------------
    # a zipf(s=1.1) popularity law over the chunk grid is the stationary
    # request mix of a large viewer population; seeded, so CI replays
    # the identical herd every run
    ranks = np.arange(1, len(herd_pool) + 1, dtype=np.float64)
    pop = 1.0 / ranks ** 1.1
    pop /= pop.sum()
    order = rng.permutation(len(herd_pool))  # popularity != grid order
    draws = rng.choice(len(herd_pool), size=args.requests, p=pop)
    requests = [herd_pool[order[d]] for d in draws]
    distinct = sorted(set(requests))

    per_client = [requests[i::args.clients] for i in range(args.clients)]
    statuses = []
    lock = threading.Lock()
    barrier = threading.Barrier(args.clients)

    def viewer(ci):
      got = []
      conns = {}
      barrier.wait()
      for j, key in enumerate(per_client[ci]):
        port = ports[(ci + j) % len(ports)]  # LB round-robin
        conn = conns.get(port)
        if conn is None:
          conn = conns[port] = http.client.HTTPConnection(
            "127.0.0.1", port, timeout=60
          )
        try:
          conn.request("GET", f"/{key}", headers={"Accept-Encoding": "gzip"})
          resp = conn.getresponse()
          resp.read()
          got.append(resp.status)
        except Exception:
          conns.pop(port).close()
          got.append(-1)
      for conn in conns.values():
        conn.close()
      with lock:
        statuses.extend(got)

    threads = [
      threading.Thread(target=viewer, args=(ci,))
      for ci in range(args.clients)
    ]
    t0 = time.perf_counter()
    for t in threads:
      t.start()
    for t in threads:
      t.join()
    herd_sec = time.perf_counter() - t0
    assert all(s == 200 for s in statuses), (
      f"non-200 in herd: {sorted(set(statuses))}"
    )
    rps = len(requests) / herd_sec
    print(f"herd: {len(requests)} requests ({len(distinct)} distinct chunks) "
          f"in {herd_sec:.2f}s = {rps:.0f} req/s")

    # ---- byte identity on every replica ----------------------------------
    for key in distinct[:8]:
      stored, _ = cf.get_stored(key)
      etag = strong_etag(stored)
      for port in ports:
        status, headers, body = get(
          port, f"/{key}", {"Accept-Encoding": "gzip"}
        )
        assert status == 200 and body == stored, (
          f"{key} differs on :{port}"
        )
        assert headers["ETag"] == etag
    print("byte identity: 8 chunks x 3 replicas, all == stored bytes")

    # ---- headline economics: 1 origin fetch per distinct cold chunk ------
    deadline = time.time() + 45
    totals = {}
    while time.time() < deadline:
      totals = aggregate_counters(jpath)
      if totals.get("serve.fetch", 0) >= len(distinct):
        break
      time.sleep(1.0)
    assert totals.get("serve.fetch", 0) == len(distinct), (
      f"fleet-wide origin fetches {totals.get('serve.fetch')} != "
      f"{len(distinct)} distinct cold chunks — federation leaked to origin"
    )
    peer_hits = totals.get("serve.peer.hits", 0)
    assert peer_hits > 0, "no peer fills at all — the ring never engaged"
    fills = peer_hits + totals.get("serve.fetch", 0)
    peer_hit_ratio = peer_hits / fills
    print(f"economics: origin fetches == {len(distinct)} distinct chunks, "
          f"peer fills {peer_hits} (peer-hit ratio {peer_hit_ratio:.2f})")

    # metrics port satellite: the fleet gauges are scrapeable
    _, _, mbody = get(infos[0]["metrics_port"], "/metrics")
    assert b"igneous_serve_fleet_peers_live" in mbody, (
      "metrics exposition lacks igneous_serve_fleet_peers_live"
    )

    # ---- drain one replica: the fleet keeps serving ----------------------
    ring = HashRing(urls)
    victim_idx = urls.index(ring.owner(layer_name, reserved[0]))
    victim = procs[victim_idx]
    victim.send_signal(signal.SIGTERM)
    rc = victim.wait(timeout=60)
    assert rc == 0, f"drained replica exited {rc} (want 0)"
    survivors = [p for i, p in enumerate(ports) if i != victim_idx]
    for key in reserved:  # includes chunks the dead replica owned
      stored, _ = cf.get_stored(key)
      status, _, body = get(
        survivors[0], f"/{key}", {"Accept-Encoding": "gzip"}
      )
      assert status == 200 and body == stored, (
        f"fleet lost {key} after draining one replica"
      )
    print("drain: SIGTERM'd the owner of reserved chunks, "
          "survivors still serve them byte-identically")

    report.update({
      "serve_fleet_req_per_sec": round(rps, 1),
      "distinct_chunks": len(distinct),
      "origin_fetches": totals.get("serve.fetch", 0),
      "peer_hits": peer_hits,
      "peer_hit_ratio": round(peer_hit_ratio, 4),
      "coalesce_leaders": totals.get("serve.coalesce.leaders", 0),
      "drained_replica": urls[victim_idx],
    })
  finally:
    for proc in procs:
      if proc.poll() is None:
        proc.send_signal(signal.SIGTERM)
    for proc in procs:
      if proc.poll() is None:
        try:
          proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
          proc.kill()

  # ---- phase 2: forced overload must shed, not melt ----------------------
  jpath_qos = f"file://{tmp}/journal-qos"
  proc, info = boot_replica(layer_path, jpath_qos, members=None, extra_env={
    "IGNEOUS_SERVE_QOS_RPS": "10",
    "IGNEOUS_SERVE_QOS_BURST_SEC": "1",
    "IGNEOUS_SERVE_QOS_WEIGHTS": "layer=1",
  })
  try:
    port = info["port"]
    status, _, _ = get(port, f"/{chunks[0]}")
    assert status == 200, "first request within burst must be admitted"
    sheds = 0
    retry_after = None
    for _ in range(80):
      status, headers, _ = get(port, f"/{chunks[0]}")
      if status == 503:
        sheds += 1
        retry_after = headers.get("Retry-After")
    assert sheds > 0, "forced overload (80 req @ 10 rps) never shed"
    assert retry_after and int(retry_after) >= 1, retry_after
    shed_rate = sheds / 81.0
    print(f"overload: {sheds}/81 shed with Retry-After={retry_after}s")
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=60)
    assert rc == 0, f"QoS replica exited {rc}"
  finally:
    if proc.poll() is None:
      proc.kill()
      proc.wait(timeout=30)

  qos_totals = aggregate_counters(jpath_qos)
  assert qos_totals.get("serve.shed.requests", 0) == sheds, (
    f"journaled sheds {qos_totals.get('serve.shed.requests')} != {sheds}"
  )
  report.update({
    "shed_rate_under_overload": round(shed_rate, 4),
    "sheds": sheds,
  })

  with open(args.report_out, "w") as f:
    json.dump(report, f, indent=2, sort_keys=True)
  print(f"report -> {args.report_out}")
  print("serve fleet smoke OK")


if __name__ == "__main__":
  main()
