"""Compilations the device ledger recorded inside the window. Every
program is compiled or loaded in set-up: this should read 0."""


def read(ctx):
  return ctx["window"]["compiles"]
