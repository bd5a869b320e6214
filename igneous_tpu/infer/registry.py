"""Model registry/loader: named conv-net architectures as pure JAX apply
functions plus parameters stored as Precomputed-style objects.

A model lives at a cloudpath (any storage backend) as two objects:

  model.json   — ModelSpec: architecture name, channel widths, patch
                 geometry, overlap (all the wire-schema facts a worker
                 needs to tile and blend)
  params.npz   — flat {param_name: float32 array} dict (np.savez)

Architectures are PURE functions ``apply(params, x)`` on one patch in
device layout ``(c, z, y, x)`` returning ``(out_channels, z, y, x)`` —
no framework, no mutable state — so they batch through
``parallel.executor.BatchKernelExecutor`` (vmap + shard_map) and the
params ride as a replicated ``consts`` pytree. The jitted program is
cached per (patch signature, params signature) in the executor, so PR 7's
``device.compile`` / recompile ledger accounts model compiles exactly like
every other kernel.

Chunkflow (PAPERS.md) is the shape reference: patch-wise conv-net
inference over chunked volumes; here the net itself is deliberately
framework-free JAX.
"""

from __future__ import annotations

import io
import json
import threading
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Sequence, Tuple

import numpy as np

from ..storage import CloudFiles

MODEL_SPEC_KEY = "model.json"
MODEL_PARAMS_KEY = "params.npz"


@dataclass(frozen=True)
class ModelSpec:
  """Wire description of a registered model (model.json)."""

  architecture: str
  in_channels: int
  out_channels: int
  patch_shape: Tuple[int, int, int]       # (x, y, z) voxels per patch
  overlap: Tuple[int, int, int] = (0, 0, 0)  # (x, y, z) blend overlap
  hidden: Tuple[int, ...] = ()            # conv stack widths (convnet3d)
  metadata: dict = field(default_factory=dict)

  def to_dict(self) -> dict:
    return {
      "architecture": self.architecture,
      "in_channels": int(self.in_channels),
      "out_channels": int(self.out_channels),
      "patch_shape": [int(v) for v in self.patch_shape],
      "overlap": [int(v) for v in self.overlap],
      "hidden": [int(v) for v in self.hidden],
      "metadata": dict(self.metadata),
    }

  @classmethod
  def from_dict(cls, d: dict) -> "ModelSpec":
    return cls(
      architecture=d["architecture"],
      in_channels=int(d["in_channels"]),
      out_channels=int(d["out_channels"]),
      patch_shape=tuple(int(v) for v in d["patch_shape"]),
      overlap=tuple(int(v) for v in d.get("overlap", (0, 0, 0))),
      hidden=tuple(int(v) for v in d.get("hidden", ())),
      metadata=dict(d.get("metadata", {})),
    )


# -- architectures ----------------------------------------------------------

ARCHITECTURES: Dict[str, Callable] = {}


def register_architecture(name: str):
  def deco(builder):
    ARCHITECTURES[name] = builder
    return builder
  return deco


@register_architecture("identity")
def _identity(spec: ModelSpec):
  """Pass-through (float32 cast only). The byte-determinism and blend
  identity contracts are provable against it because the device output
  IS the input — any non-identity byte came from the engine."""
  if spec.out_channels != spec.in_channels:
    raise ValueError("identity requires out_channels == in_channels")

  def apply(params, x):
    del params
    return x.astype("float32")

  return apply


@register_architecture("convnet3d")
def _convnet3d(spec: ModelSpec):
  """Plain 3x3x3 conv stack with ReLU between layers (none after the
  last): widths ``in -> hidden... -> out``, SAME padding so output
  geometry equals patch geometry. Parameters: ``layer{i}/w`` with shape
  (c_out, c_in, 3, 3, 3) and ``layer{i}/b`` with shape (c_out,)."""
  import jax.numpy as jnp
  from jax import lax

  widths = (spec.in_channels,) + tuple(spec.hidden) + (spec.out_channels,)
  n_layers = len(widths) - 1

  def apply(params, x):
    # x: (c, z, y, x) one patch; conv wants an explicit batch dim
    h = x.astype(jnp.float32)[None]
    for i in range(n_layers):
      h = lax.conv_general_dilated(
        h, params[f"layer{i}/w"].astype(jnp.float32),
        window_strides=(1, 1, 1), padding="SAME",
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
      )
      h = h + params[f"layer{i}/b"].astype(jnp.float32)[None, :, None, None, None]
      if i < n_layers - 1:
        h = jnp.maximum(h, 0.0)
    return h[0]

  return apply


def init_params(spec: ModelSpec, seed: int = 0) -> Dict[str, np.ndarray]:
  """Deterministic He-style float32 init for the named architecture.
  Fixed-seed models back the bench and CI smoke — same seed, same bytes."""
  rng = np.random.default_rng(seed)
  if spec.architecture == "identity":
    return {}
  if spec.architecture == "convnet3d":
    widths = (spec.in_channels,) + tuple(spec.hidden) + (spec.out_channels,)
    params = {}
    for i in range(len(widths) - 1):
      c_in, c_out = widths[i], widths[i + 1]
      fan_in = c_in * 27
      params[f"layer{i}/w"] = (
        rng.standard_normal((c_out, c_in, 3, 3, 3)) * np.sqrt(2.0 / fan_in)
      ).astype(np.float32)
      params[f"layer{i}/b"] = np.zeros(c_out, dtype=np.float32)
    return params
  raise KeyError(f"no init rule for architecture {spec.architecture!r}")


# -- persistence ------------------------------------------------------------

def save_model(
  cloudpath: str, spec: ModelSpec, params: Dict[str, np.ndarray]
) -> None:
  """Write model.json + params.npz under ``cloudpath``."""
  if spec.architecture not in ARCHITECTURES:
    raise KeyError(
      f"unknown architecture {spec.architecture!r}; "
      f"registered: {sorted(ARCHITECTURES)}"
    )
  cf = CloudFiles(cloudpath)
  cf.put(MODEL_SPEC_KEY, json.dumps(spec.to_dict()).encode("utf8"))
  buf = io.BytesIO()
  np.savez(buf, **{k: np.asarray(v) for k, v in params.items()})
  cf.put(MODEL_PARAMS_KEY, buf.getvalue())
  # a new model at a previously-seen path must not serve stale weights
  with _CACHE_LOCK:
    _MODEL_CACHE.pop(cloudpath.rstrip("/"), None)


class InferenceModel:
  """A loaded (spec, params, apply) triple bound to its cloudpath.

  Executors are cached per (cloudpath, mesh) so repeated tasks in one
  worker share the jit cache — the whole point of jitting once per patch
  signature — and params are device-staged once via ``put_consts``."""

  def __init__(self, cloudpath: str, spec: ModelSpec,
               params: Dict[str, np.ndarray]):
    self.cloudpath = cloudpath
    self.spec = spec
    self.params = params
    builder = ARCHITECTURES.get(spec.architecture)
    if builder is None:
      raise KeyError(
        f"unknown architecture {spec.architecture!r}; "
        f"registered: {sorted(ARCHITECTURES)}"
      )
    self.apply = builder(spec)
    self._lock = threading.Lock()
    self._executors = {}  # guarded-by: self._lock

  @property
  def kernel_name(self) -> str:
    return f"infer.{self.spec.architecture}"

  def executor(self, mesh=None):
    from ..parallel.executor import BatchKernelExecutor, make_mesh

    mesh = mesh if mesh is not None else make_mesh()
    key = tuple(d.id for d in mesh.devices.flat)
    with self._lock:
      if key not in self._executors:
        self._executors[key] = BatchKernelExecutor(
          self.apply, mesh=mesh, name=self.kernel_name,
        )
      return self._executors[key]

  def device_params(self, mesh=None):
    """Params staged on device (replicated), h2d paid once per model."""
    return self.executor(mesh).put_consts(self.cloudpath, self.params)


_MODEL_CACHE: Dict[str, InferenceModel] = {}
_CACHE_LOCK = threading.Lock()


def load_model(cloudpath: str) -> InferenceModel:
  """Load (and process-wide cache) the model at ``cloudpath``."""
  key = cloudpath.rstrip("/")
  with _CACHE_LOCK:
    cached = _MODEL_CACHE.get(key)
  if cached is not None:
    return cached
  cf = CloudFiles(cloudpath)
  raw = cf.get(MODEL_SPEC_KEY)
  if raw is None:
    raise FileNotFoundError(f"no {MODEL_SPEC_KEY} at {cloudpath}")
  spec = ModelSpec.from_dict(json.loads(raw.decode("utf8")))
  blob = cf.get(MODEL_PARAMS_KEY)
  if blob is None:
    raise FileNotFoundError(f"no {MODEL_PARAMS_KEY} at {cloudpath}")
  with np.load(io.BytesIO(blob)) as npz:
    params = {k: np.asarray(npz[k]) for k in npz.files}
  model = InferenceModel(key, spec, params)
  with _CACHE_LOCK:
    _MODEL_CACHE[key] = model
  return model


def clear_model_cache() -> None:
  with _CACHE_LOCK:
    _MODEL_CACHE.clear()
