"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

    python3 benchmark/tests/record_small_trace.py <out_dir>

Run on a TPU host. Inside a ``bench.window`` span it dispatches a small
jitted program three times, each inside a ``bench.dispatch`` span, and
sleeps in a ``bench.download`` span between them, so the trace holds
device operations, named host spans and idle gaps. The ``.xplane.pb``
is copied to ``<out_dir>/small.xplane.pb`` with a JSON summary beside it.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time


def main(out_dir: str) -> int:
  import jax
  import jax.numpy as jnp

  if jax.devices()[0].platform != "tpu":
    print("record_small_trace: no TPU", file=sys.stderr)
    return 2
  os.makedirs(out_dir, exist_ok=True)
  f = jax.jit(lambda x: (x.reshape(512, 2, 512, 2).mean(axis=(1, 3)) * 2).sum())
  x = jnp.ones((1024, 1024), jnp.float32)
  f(x).block_until_ready()
  opts = jax.profiler.ProfileOptions()
  opts.python_tracer_level = 0
  opts.enable_hlo_proto = False
  tmp = tempfile.mkdtemp()
  jax.profiler.start_trace(tmp, profiler_options=opts)
  with jax.profiler.TraceAnnotation("bench.window"):
    for _ in range(3):
      with jax.profiler.TraceAnnotation("bench.download"):
        time.sleep(0.02)
      with jax.profiler.TraceAnnotation("bench.dispatch"):
        f(x).block_until_ready()
  jax.profiler.stop_trace()
  path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)[0]
  dst = os.path.join(out_dir, "small.xplane.pb")
  shutil.copy(path, dst)
  shutil.rmtree(tmp, ignore_errors=True)

  from jax.profiler import ProfileData

  summary = []
  for plane in ProfileData.from_file(dst).planes:
    lines = []
    for line in plane.lines:
      events = [(e.name, e.start_ns, e.duration_ns) for e in line.events]
      lines.append({"line": line.name, "events": len(events),
                    "first": events[:3]})
    summary.append({"plane": plane.name, "lines": lines})
  with open(os.path.join(out_dir, "small.summary.json"), "w") as fh:
    json.dump(summary, fh, indent=1, default=str)
  print(json.dumps({"trace": dst, "bytes": os.path.getsize(dst)}))
  return 0


if __name__ == "__main__":
  sys.exit(main(sys.argv[1]))
