"""Process start to window open (host clock): JAX and chip start-up,
source generation or load, planning and the warm-up round."""


def read(ctx):
  return ctx["setup_s"]
