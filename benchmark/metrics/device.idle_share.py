"""Percent of the traced window in which no operation ran on the device,
averaged over the cell's chips (profiler trace)."""


def read(ctx):
  trace = ctx["trace"]
  if trace is None or trace["window_s"] <= 0 or not trace["devices"]:
    return None
  return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
