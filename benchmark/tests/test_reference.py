"""The plain reference agrees with the program's own oracles and codecs
at small sizes, ties and odd extents included (a test may import the
program; the reference itself does not)."""

import numpy as np
import pytest

from benchmark import reference


@pytest.mark.parametrize("shape", [(16, 12, 4), (15, 9, 3)])
@pytest.mark.parametrize("factor", [(2, 2, 1), (2, 2, 2)])
def test_pyramid_matches_program_oracles(shape, factor):
  from igneous_tpu.ops import oracle

  rng = np.random.default_rng(0)
  labels = rng.integers(0, 4, shape).astype(np.uint64) * np.uint64(2**33)
  image = rng.integers(0, 256, shape).astype(np.uint8)
  got = reference.pyramid(labels, [factor] * 2, "mode")
  want = oracle.np_downsample_segmentation(labels, factor, 2)
  assert all(np.array_equal(a, b) for a, b in zip(got, want))
  got = reference.pyramid(image, [factor] * 2, "average")
  want = oracle.np_downsample_with_averaging(image, factor, 2)
  assert all(np.array_equal(a, b) for a, b in zip(got, want))


def test_low_precision_differs():
  rng = np.random.default_rng(1)
  labels = rng.integers(2**32, 2**40, (8, 8, 2)).astype(np.uint64)
  image = rng.integers(0, 256, (64, 64, 4)).astype(np.uint8)
  for arr, method in ((labels, "mode"), (image, "average")):
    exact = reference.pyramid(arr, [(2, 2, 1)], method)
    low = reference.pyramid(arr, [(2, 2, 1)], method, precision="low")
    assert not np.array_equal(exact[0], low[0])


@pytest.mark.parametrize("shape", [(32, 32, 16), (20, 12, 8), (128, 128, 64)])
def test_decoders_match_program_codecs(shape):
  """The reference's jpeg codec (OpenCV's libjpeg) gives the same pixels
  as the program's (PIL's), on noise and on the EM image at the cell's
  chunk size: the exact comparison does not rest on one libjpeg build."""
  from igneous_tpu import codecs

  from benchmark.gen import em_image

  rng = np.random.default_rng(2)
  labels = rng.integers(0, 9, shape).astype(np.uint64) + np.uint64(2**40)
  data = codecs.encode(labels[..., None], "compressed_segmentation")
  assert np.array_equal(reference.decode_cseg(data, shape, np.uint64), labels)
  image = (em_image.generate(shape, 2) if shape == (128, 128, 64)
           else rng.integers(0, 256, shape).astype(np.uint8))
  data = codecs.encode(image[..., None], "jpeg", jpeg_quality=85)
  want = codecs.decode(data, "jpeg", shape, np.uint8)[..., 0]
  assert np.array_equal(reference.decode_jpeg(data, shape), want)
  assert np.array_equal(reference.jpeg_round_trip(image, 85), want)
