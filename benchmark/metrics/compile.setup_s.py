"""Seconds the device ledger recorded compiling (or loading from JAX's
persistent cache) during set-up."""


def read(ctx):
  return ctx["setup"]["compile_s"]
