"""The uint8 batch stack's per-layer metric: a traced tiny uint8 run
reads ``executor.stack_s_per_gvox`` from the program's ``pooling.stack``
span, and the reader reports nothing where the span is absent, as in a
program that lacks it.

    python -m pytest benchmark/tests/test_stack_reader.py -q
"""

import pytest

from helpers import make_checkout, run_cell

SEED = 3_000_000_027


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
  return make_checkout(str(tmp_path_factory.mktemp("checkout")))


def test_traced_em_run_reads_stack(checkout):
  res = run_cell(checkout, "tiny_em.k2", SEED, seconds=2, trace=1)
  assert res["correct"], res["checks"]
  assert res["metrics"]["executor.stack_s_per_gvox"]["value"] > 0


def _reduced(seconds):
  return {"seconds": seconds, "download_voxels": 2e9, "executes": {},
          "modules_s": {}}


@pytest.mark.parametrize("reduced,want", [
  (_reduced({"pooling.stack": 0.5, "pooling.u64_split": 1.0}), 0.25),
  (_reduced({"pooling.u64_split": 1.0, "pooling.u64_pack": 0.5}), None),
  (_reduced({}), None),
  (None, None),
])
def test_stack_reader(monkeypatch, checkout, reduced, want):
  from benchmark import span_reduce
  from benchmark.spec import Spec

  monkeypatch.setattr(span_reduce, "for_run", lambda ctx, f: reduced)
  reader = Spec(checkout).reader("executor.stack_s_per_gvox")
  assert reader.read({}) == want
