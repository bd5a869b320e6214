"""Seconds in the queue's lease, renew, delete, nack and release calls,
timed by the harness around the queue the worker loop is handed, per
acknowledged task."""


def read(ctx):
  win = ctx["window"]
  if not win["acked"]:
    return None
  return win["queue_s"] / win["acked"]
