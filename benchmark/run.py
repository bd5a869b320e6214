"""Run one benchmark cell and print its result as the last line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic are found by name through
``BENCHMARK.json`` (see ``benchmark/spec.py``). One process holds the
chips: it builds the cell's source from the seed, plans and warms up
(set-up), drives the campaign for ``--seconds`` (the window), then checks
what the window stored against the plain reference.

With ``--trace 0`` the metrics are the cell's end-to-end ones; with
``--trace 1`` the window runs under the profiler and the metrics are the
per-layer ones, read from the trace, the program's counters and the
harness's own timing. JAX's compile cache lives at
``benchmark/.cache/jax`` in the checkout. With no TPU, or fewer chips than
the cell asks for, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

PROCESS_T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
  pass


def _log(msg: str) -> None:
  print(msg, file=sys.stderr, flush=True)


def _args(argv):
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  ap.add_argument("--control", action="store_true",
                  help="store the reference's low-precision pyramid in "
                       "place of the window and check that (no metrics)")
  return ap.parse_args(argv)


def _place_cache(cache_dir: str) -> None:
  """JAX's persistent compile cache at a fixed path in the checkout; set
  before the program imports JAX, so the program takes this one."""
  jax_dir = os.path.join(cache_dir, "jax")
  os.makedirs(jax_dir, exist_ok=True)
  os.environ["JAX_COMPILATION_CACHE_DIR"] = jax_dir
  ignore = os.path.join(cache_dir, ".gitignore")
  if not os.path.exists(ignore):
    with open(ignore, "w") as f:
      f.write("*\n")
  from igneous_tpu.compile_cache import place_jax_cache

  place_jax_cache()


def _devices(chips: int, require_chip: bool):
  import jax

  devices = jax.devices()
  if require_chip and devices[0].platform != "tpu":
    raise NoChip(f"no TPU: JAX platform is {devices[0].platform!r}")
  if len(devices) < chips:
    raise NoChip(f"the cell asks for {chips} chips, {len(devices)} present")
  return devices[:chips]


def _memory_peak(devices):
  peaks = []
  for d in devices:
    try:
      stats = d.memory_stats() or {}
    except Exception:  # a backend without memory stats
      stats = {}
    if "peak_bytes_in_use" in stats:
      peaks.append(int(stats["peak_bytes_in_use"]))
  return max(peaks) if peaks else None


def _trace_options():
  import jax

  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  opts.enable_hlo_proto = False
  return opts


def _checks(numbers: dict, win) -> dict:
  """Each number compared, beside its limit. The comparison is exact, so
  its limit is 0: the sound runs read 0, the control reads millions. A
  window that decoded a source chunk from the cache did less than the
  campaign's work, so the cache's hits are held to 0 too."""
  checks = {
    "mismatched_voxels": {"value": numbers["mismatched_voxels"], "limit": 0},
  }
  if win is not None:
    checks["src_chunk_cache_hits"] = {"value": win["src_cache_hits"],
                                      "limit": 0}
  return checks


def _correct(numbers: dict, checks: dict) -> bool:
  """Within every limit, and something was compared: a window that
  stored nothing proves nothing."""
  return numbers["voxels_compared"] > 0 and all(
    c["value"] <= c["limit"] for c in checks.values())


def run(argv=None, require_chip: bool = True, root: str = ROOT,
        bench_dir: str = BENCH_DIR) -> dict:
  """One run; returns the result object. ``require_chip=False`` lets a
  test drive the whole run on JAX's CPU backend."""
  args = _args(argv)
  if root not in sys.path:
    sys.path.insert(0, root)
  from benchmark.spec import Spec

  spec = Spec(root, bench_dir)
  cell = spec.cell(args.workload)
  cache_dir = os.path.join(bench_dir, ".cache")
  _place_cache(cache_dir)
  devices = _devices(int(cell["chips"]), require_chip)
  ctx = types.SimpleNamespace(
    spec=spec, cell=cell, config_name=cell["config"],
    config=spec.config(cell["config"]), traffic=spec.traffic(cell["traffic"]),
    seed=args.seed, chips=int(cell["chips"]), cache_dir=cache_dir,
  )
  driver = spec.driver(ctx.traffic["driver"])
  from benchmark.probes import ledger_totals

  init_s = time.perf_counter() - PROCESS_T0
  campaign = driver.Campaign(ctx)
  try:
    split = campaign.setup()
    setup_ledger = ledger_totals()
    setup_s = time.perf_counter() - PROCESS_T0
    split = dict(init_s=init_s, **split, setup_s=setup_s,
                 compile_s=setup_ledger["compile_s"])
    print(json.dumps({"setup": split}), flush=True)
    if args.control:
      campaign.plant_control()
      win = None
    else:
      reduced = None
      trace_dir = os.path.join(cache_dir, "run", cell["name"] + ".trace")
      if args.trace:
        import jax

        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
      try:
        win = campaign.window(args.seconds, traced=bool(args.trace))
      finally:
        if args.trace:
          jax.profiler.stop_trace()
      if args.trace:
        from benchmark import trace_reduce

        path = trace_reduce.find_xplane(trace_dir)
        reduced = trace_reduce.reduce_trace(path) if path else None
    peak = _memory_peak(devices)
    campaign.release()
    t_check = time.perf_counter()
    numbers = campaign.check()
    check_s = time.perf_counter() - t_check
    print(json.dumps({"check": dict(numbers, check_s=check_s)}), flush=True)
  finally:
    campaign.close()
  checks = _checks(numbers, win)
  dev = devices[0]
  device = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak}
  result = {"correct": _correct(numbers, checks), "attempted": 0, "failed": 0,
            "metrics": {}, "device": device}
  if win is not None:
    result["attempted"] = win["attempted"]
    result["failed"] = win["failed"]
    mctx = {
      "cell": cell, "config": ctx.config, "traffic": ctx.traffic,
      "chips": ctx.chips, "device_kind": dev.device_kind,
      "setup_s": setup_s, "setup": split, "window": win,
      "probes": campaign.probes, "trace": reduced,
      "method": campaign.method,
      "kernel_bytes_per_dispatch": campaign.kernel_bytes_per_dispatch(),
    }
    for entry in spec.metrics(cell["name"], trace=bool(args.trace)):
      value = spec.reader(entry["name"]).read(mctx)
      if value is not None:
        result["metrics"][entry["name"]] = {"value": value,
                                            "unit": entry["unit"]}
    if args.trace and reduced is not None:
      from benchmark import trace_reduce

      device["busy_s"] = reduced["busy_s"]
      device["window_s"] = reduced["window_s"]
      result["breakdown"] = trace_reduce.breakdown(reduced)
    result["window"] = {k: win[k] for k in (
      "window_s", "voxels", "acked", "dispatches", "compiles", "queue_s")}
  result["checks"] = checks
  return result


def main(argv=None) -> int:
  try:
    result = run(argv)
  except NoChip as e:
    _log(f"benchmark: {e}")
    return EXIT_NO_CHIP
  for name, c in result["checks"].items():
    _log(f"check {name} {c['value']} limit {c['limit']}")
  print(json.dumps(result), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
