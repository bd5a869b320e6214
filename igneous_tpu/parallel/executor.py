"""Batched, mesh-sharded execution of per-chunk kernels.

Chunks are this domain's batch dimension. A host leases K grid tasks,
stacks their equally-shaped cutouts into a (K, c, z, y, x) array, and runs
the pooling pyramid once, shard_map-ed over the mesh's "chunks" axis so
each TPU core processes K/n chunks. Collectives (psum over ICI) aggregate
scalar statistics (voxel counts, histograms) without host round-trips.
"""

from __future__ import annotations

import re
import threading
from collections import OrderedDict
from functools import partial
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..analysis import knobs
from ..observability import device as device_telemetry
from ..ops.pooling import _pyramid_impl, device_memory_bytes, slab_depth



def program_name(kernel: str) -> str:
  """The Python name a kernel's compiled program is built from:
  ``pooling.pyramid[average]`` -> ``pooling_pyramid_average``. JAX names
  the program's module ``jit_<program_name>``, which is how a profiler
  trace finds the kernel's device time by name."""
  return re.sub(r"\W+", "_", kernel).strip("_") or "kernel"


def _named_jit(fn, kernel: str):
  """``jax.jit`` of ``fn`` under the kernel's stable program name, with
  its operations inside ``jax.named_scope`` of the same name."""
  name = program_name(kernel)

  def program(*args):
    with jax.named_scope(name):
      return fn(*args)

  program.__name__ = program.__qualname__ = name
  # lint: allow=IGN201 AOT lower+compile cached by signature at call site
  return jax.jit(program)


def compile_program(kernel: str, signature, mesh, lower):
  """The executors' one compile route: tick the recompile ledger for
  ``signature``, then compile ``lower()`` (a ``jax.stages.Lowered``)
  ahead of time inside a ``device.compile`` span. Compiling apart from
  the first call keeps XLA's work out of the first ``device.execute``
  span; JAX's persistent cache (``compile_cache.place_jax_cache``)
  serves a program another process already compiled."""
  device_telemetry.LEDGER.note_signature(kernel, signature)
  with device_telemetry.compile_span(
    kernel, device_telemetry._devices_of(mesh)
  ):
    return lower().compile()


def make_mesh(n_devices: Optional[int] = None, axis: str = "chunks") -> Mesh:
  devices = jax.devices()
  if n_devices is not None:
    devices = devices[:n_devices]
  return Mesh(np.asarray(devices), (axis,))


class LRUCache:
  """Bounded mapping for per-process compiled-executable caches
  (ISSUE 19 satellite): a long-lived worker that drifts through many
  signatures must not hold every executable it ever compiled. Cap from
  ``IGNEOUS_EXECUTOR_CACHE_CAP``; least-recently-USED eviction (both
  lookup and insert refresh recency). Eviction is safe — a re-needed
  signature recompiles without a fresh ``device.recompiles`` tick,
  since the ledger seen-set is independent of this cache."""

  def __init__(self, cap: Optional[int] = None):
    if cap is None:
      cap = knobs.get_int("IGNEOUS_EXECUTOR_CACHE_CAP")
    self.cap = max(int(cap or 64), 1)
    self._d: OrderedDict = OrderedDict()

  def __contains__(self, key) -> bool:
    return key in self._d

  def __len__(self) -> int:
    return len(self._d)

  def __getitem__(self, key):
    val = self._d[key]
    self._d.move_to_end(key)
    return val

  def get(self, key, default=None):
    if key not in self._d:
      return default
    return self[key]

  def __setitem__(self, key, val) -> None:
    self._d[key] = val
    self._d.move_to_end(key)
    while len(self._d) > self.cap:
      self._d.popitem(last=False)

  def keys(self):
    return self._d.keys()


_CHUNK_EXECUTOR_CACHE = {}
# input signatures a ChunkExecutor keeps free host batches for: a lease
# batcher's full rounds and its last, shorter one
STAGING_SIGNATURES = 2


def cached_chunk_executor(
  mesh: Optional[Mesh] = None,
  factors: Sequence[Tuple[int, int, int]] = ((2, 2, 1),),
  method: str = "average",
  sparse: bool = False,
  planes: int = 1,
) -> "ChunkExecutor":
  """ChunkExecutor instances keyed by (devices, axis, pyramid config).

  Each instance owns a fresh shard_map'd jit closure, so constructing one
  per call recompiles the pyramid every time — repeat callers
  (batched_downsample per lease batch) must share instances to hit the
  jit cache."""
  mesh = mesh if mesh is not None else make_mesh()
  key = (
    tuple(d.id for d in mesh.devices.flat), mesh.axis_names,
    tuple(tuple(int(v) for v in f) for f in factors), method, sparse,
    int(planes),
  )
  if key not in _CHUNK_EXECUTOR_CACHE:
    _CHUNK_EXECUTOR_CACHE[key] = ChunkExecutor(
      mesh, factors=factors, method=method, sparse=sparse, planes=planes
    )
  return _CHUNK_EXECUTOR_CACHE[key]


class BatchKernelExecutor:
  """shard_map + vmap wrapper for ANY per-chunk device kernel.

  Generalizes ChunkExecutor's lease-K → one-dispatch pattern beyond
  pooling (VERDICT round-1 item 3): the kernel is an arbitrary jax
  function on one chunk (pytree in, pytree out, batch-uniform shapes);
  this runs it for K chunks in a single compiled program with the chunk
  axis partitioned across the mesh over ICI. Compiled variants are cached
  per input signature.

  ``consts`` (ISSUE 10): a non-batched pytree — model parameters — passed
  as ``kernel(consts, chunk)`` and replicated across the mesh instead of
  partitioned. Passing params as a runtime argument (``in_axes=(None, 0)``)
  rather than closing over them keeps the compiled program
  params-independent: one model reload or A/B swap does not recompile,
  and XLA never bakes megabytes of weights into the executable as
  literals. Pre-stage them once with :meth:`put_consts` so the h2d cost
  is paid per model, not per dispatch.
  """

  def __init__(self, kernel, mesh: Optional[Mesh] = None,
               name: Optional[str] = None):
    self.kernel = kernel
    self.name = name or getattr(kernel, "__name__", "kernel").lstrip("_")
    self.mesh = mesh if mesh is not None else make_mesh()
    self.axis = self.mesh.axis_names[0]
    self._cache = LRUCache()
    self._consts_cache = LRUCache()

  @property
  def n_devices(self) -> int:
    return int(np.prod(self.mesh.devices.shape))

  def _signature(self, batch):
    leaves, treedef = jax.tree.flatten(batch)
    return (treedef, tuple((l.shape, str(l.dtype)) for l in leaves))

  def put_consts(self, key, consts):
    """Stage a consts pytree on device, replicated over the mesh, once
    per ``key`` (callers use a stable identity such as the model
    cloudpath). Returns the device pytree to pass back as ``consts=``."""
    cache_key = (key, tuple(d.id for d in self.mesh.devices.flat))
    if cache_key not in self._consts_cache:
      consts = jax.tree.map(np.asarray, consts)
      replicated = NamedSharding(self.mesh, P())
      with device_telemetry.transfer_span(
        "h2d", device_telemetry.nbytes_of(consts), kernel=self.name,
        mesh=self.mesh,
      ):
        self._consts_cache[cache_key] = jax.tree.map(
          lambda a: jax.device_put(a, replicated), consts
        )
    return self._consts_cache[cache_key]

  def _build(self, example, consts=None):
    if consts is None:
      batched = jax.vmap(self.kernel)
      out_shape = jax.eval_shape(batched, example)
      in_specs = P(self.axis)
    else:
      batched = jax.vmap(self.kernel, in_axes=(None, 0))
      out_shape = jax.eval_shape(batched, consts, example)
      # P() prefix: the whole consts pytree is replicated, only the
      # chunk batch is partitioned over the mesh axis
      in_specs = (P(), P(self.axis))
    out_specs = jax.tree.map(lambda _: P(self.axis), out_shape)
    # check_vma off: kernels here are pure per-chunk programs with no
    # collectives, but their internal scan/while carries start from
    # literals, which the varying-manual-axes checker rejects under
    # shard_map (carry input unvarying vs output varying)
    fn = jax.shard_map(
      batched, mesh=self.mesh,
      in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )
    return _named_jit(fn, self.name)

  def __call__(self, batch, consts=None, span_attrs=None):
    """batch: pytree of (K, ...) arrays → pytree of (K, ...) numpy.
    ``consts``: optional non-batched pytree (see class docstring);
    device arrays from :meth:`put_consts` skip the per-call h2d.
    ``span_attrs``: extra attributes for this call's device.execute
    span (e.g. the infer engine's ``padded_slots``) — never part of
    the compile signature."""
    batch = jax.tree.map(np.asarray, batch)
    leaves = jax.tree.leaves(batch)
    k = leaves[0].shape[0]
    # canonical K: next power of two that is a mesh multiple. K is part
    # of the jit-cache signature, so uncapped ragged group sizes (e.g.
    # per-task label counts) would compile a program per K
    canon = self.n_devices
    while canon < k:
      canon <<= 1
    rem = canon - k
    if rem:
      batch = jax.tree.map(
        lambda a: np.concatenate(
          [a, np.zeros((rem,) + a.shape[1:], a.dtype)]
        ),
        batch,
      )
    # per-dispatch padding bytes (ISSUE 12): the pow2 batch rounding is
    # one of the padding layers igneous_device_pad_waste_ratio tracks
    row_bytes = sum(int(l.nbytes) // max(k, 1) for l in leaves)
    device_telemetry.LEDGER.record_pad_waste(
      padded_bytes=rem * row_bytes, real_bytes=k * row_bytes,
    )
    if consts is not None:
      # numpy consts are staged ad hoc (keyed by leaf identity); callers
      # with a stable model identity use put_consts() for real reuse
      leaves = jax.tree.leaves(consts)
      if any(isinstance(l, np.ndarray) for l in leaves):
        consts = self.put_consts(tuple(id(l) for l in leaves), consts)
    sig = self._signature(batch)
    if consts is not None:
      sig = (sig, self._signature(consts))
    sharding = NamedSharding(self.mesh, P(self.axis))
    with device_telemetry.transfer_span(
      "h2d", device_telemetry.nbytes_of(batch), kernel=self.name,
      mesh=self.mesh,
    ):
      dev = jax.tree.map(lambda a: jax.device_put(a, sharding), batch)
    argv = (dev,) if consts is None else (consts, dev)
    if sig not in self._cache:
      self._cache[sig] = compile_program(
        self.name, sig, self.mesh,
        lambda: self._build(batch, consts).lower(*argv),
      )
    with device_telemetry.execute_span(
      self.name, elements=device_telemetry.elements_of(batch),
      nbytes=device_telemetry.nbytes_of(batch), mesh=self.mesh,
      **(span_attrs or {}),
    ):
      out = self._cache[sig](*argv)
      jax.block_until_ready(out)
    with device_telemetry.transfer_span(
      "d2h", device_telemetry.nbytes_of(out), kernel=self.name,
      mesh=self.mesh,
    ):
      return jax.tree.map(lambda a: np.asarray(a)[:k], out)


class ChunkExecutor:
  """Compiles and runs batched chunk pyramids over a device mesh.

  One instance per (factors, method, sparse, chunk shape, dtype) — the
  compiled program is cached by XLA across calls.
  """

  def __init__(
    self,
    mesh: Optional[Mesh] = None,
    factors: Sequence[Tuple[int, int, int]] = ((2, 2, 1),),
    method: str = "average",
    sparse: bool = False,
    planes: int = 1,
  ):
    """``planes=2`` takes (lo, hi) uint32 plane pairs — the uint64 label
    representation (see ops.pooling) — and returns per-mip plane pairs."""
    self.mesh = mesh if mesh is not None else make_mesh()
    self.factors = tuple(tuple(int(v) for v in f) for f in factors)
    self.method = method
    self.sparse = sparse
    self.planes = int(planes)
    if self.planes not in (1, 2):
      raise ValueError("planes must be 1 or 2")
    if self.planes == 2 and method != "mode":
      raise ValueError("plane pairs are only meaningful for mode pooling")
    self.axis = self.mesh.axis_names[0]
    self.name = f"pooling.pyramid[{method}]"
    # extra device.execute span attributes (mutable, not part of any
    # cache key): batched_downsample stamps the fused walk's mip range
    # ({"mip_from": m, "mip_to": m + len(factors)}) here before each run
    self.span_attrs: dict = {}
    self._fn = self._build()
    # input signature -> AOT executable (ISSUE 7); LRU-bounded (ISSUE 19)
    self._compiled = LRUCache()
    # (shape, dtype) -> host batches free for the next dispatch_staged;
    # a worker that drifts through task shapes keeps the latest few
    self._staging = LRUCache(STAGING_SIGNATURES)
    self._staging_lock = threading.Lock()

  def slab_depth(self, shard_shape, dtypes) -> int:
    """The z-slab depth the pyramid walks for one device's shard: planes
    of ``shard_shape`` (k, c, z, y, x) in ``dtypes``
    (``ops.pooling.slab_depth``). A z plane's bytes count every task and
    plane of the shard, which one slab step pools at once."""
    k, c, z, y, x = shard_shape
    plane_bytes = k * c * y * x * sum(np.dtype(d).itemsize for d in dtypes)
    return slab_depth(z, self.factors, plane_bytes,
                      device_memory_bytes(self.mesh.devices.flat[0]))

  def _walk_attrs(self, arrs) -> dict:
    """``slabs`` and ``slab_depth`` of a dispatch of global ``arrs``, for
    its device.execute span."""
    k, *rest = arrs[0].shape
    z = rest[1]
    depth = self.slab_depth((k // self.n_devices, *rest),
                            [a.dtype for a in arrs])
    return {"slabs": z // depth, "slab_depth": depth}

  def _build(self):
    factors, method, sparse = self.factors, self.method, self.sparse
    axis = self.axis
    planes = self.planes

    def per_shard(xs):  # xs: tuple of (k, c, z, y, x) local shards
      depth = self.slab_depth(xs[0].shape, [a.dtype for a in xs])

      def one(arrs):
        val = arrs if planes == 2 else arrs[0]
        return _pyramid_impl(val, factors, method, sparse, depth)

      outs = jax.vmap(lambda *arrs: one(arrs))(*xs)
      # voxel count psum: a cross-chip collective over ICI so callers get
      # a global nonzero tally with no host gather
      fg = xs[0] != 0
      for extra in xs[1:]:
        fg = fg | (extra != 0)
      nonzero = jax.lax.psum(jnp.sum(fg, dtype=jnp.int32), axis_name=axis)
      return outs, nonzero

    in_spec = tuple(P(self.axis) for _ in range(planes))
    if planes == 2:
      mip_spec = tuple((P(self.axis), P(self.axis)) for _ in factors)
    else:
      mip_spec = tuple(P(self.axis) for _ in factors)
    out_spec = (mip_spec, P())
    # check_vma off: the slab walk's mip buffers start from literals, which
    # the varying-manual-axes checker rejects as a loop carry (see
    # BatchKernelExecutor._build)
    fn = jax.shard_map(
      per_shard, mesh=self.mesh, in_specs=(in_spec,), out_specs=out_spec,
      check_vma=False,
    )
    return _named_jit(fn, self.name)

  @property
  def n_devices(self) -> int:
    return int(np.prod(self.mesh.devices.shape))

  def pad_batch(self, batch: np.ndarray) -> Tuple[np.ndarray, int]:
    """Pad the chunk axis to a multiple of the mesh size."""
    k = batch.shape[0]
    rem = (-k) % self.n_devices
    if rem:
      batch = np.concatenate([batch, np.zeros((rem,) + batch.shape[1:], batch.dtype)])
    return batch, k

  def run_global(self, global_batch):
    """Multi-host entry point: run the compiled sharded program on an
    ALREADY-sharded global jax.Array (multihost.from_process_local).
    The caller owns padding (multihost.lease_partition) and reads
    outputs through .addressable_shards — a host can only address its
    own chips, so no global gather/un-pad happens here."""
    arrs = (
      global_batch if isinstance(global_batch, tuple) else (global_batch,)
    )
    if len(arrs) != self.planes:
      raise ValueError(f"expected {self.planes} plane(s), got {len(arrs)}")
    sig = ("global",) + tuple((a.shape, str(a.dtype)) for a in arrs)
    if sig not in self._compiled:
      self._compiled[sig] = compile_program(
        self.name, sig, self.mesh, lambda: self._fn.lower(tuple(arrs)),
      )
    with device_telemetry.execute_span(
      self.name, elements=device_telemetry.elements_of(arrs),
      mesh=self.mesh, **self._walk_attrs(arrs), **self.span_attrs,
    ):
      out = self._compiled[sig](tuple(arrs))
      jax.block_until_ready(out)
    return out

  def __call__(self, batch):
    """batch: (K, c, z, y, x) array (planes=1) or a (lo, hi) tuple of such
    arrays (planes=2) → (per-mip outputs, global_nonzero). Per-mip outputs
    mirror the input arity: arrays, or (lo, hi) tuples."""
    arrs = batch if isinstance(batch, tuple) else (batch,)
    if len(arrs) != self.planes:
      raise ValueError(f"expected {self.planes} plane(s), got {len(arrs)}")
    padded = []
    k = arrs[0].shape[0]
    for a in arrs:
      p, _ = self.pad_batch(np.asarray(a))
      padded.append(p)
    real = sum(int(np.asarray(a).nbytes) for a in arrs)
    device_telemetry.LEDGER.record_pad_waste(
      padded_bytes=sum(int(p.nbytes) for p in padded) - real,
      real_bytes=real,
    )
    sharding = NamedSharding(self.mesh, P(self.axis))
    with device_telemetry.transfer_span(
      "h2d", sum(int(p.nbytes) for p in padded), kernel=self.name,
      mesh=self.mesh,
    ):
      xs = tuple(jax.device_put(p, sharding) for p in padded)
    sig = tuple((a.shape, str(a.dtype)) for a in xs)
    if sig not in self._compiled:
      self._compiled[sig] = compile_program(
        self.name, sig, self.mesh, lambda: self._fn.lower(xs),
      )
    with device_telemetry.execute_span(
      self.name, elements=sum(int(p.size) for p in padded),
      nbytes=sum(int(p.nbytes) for p in padded), mesh=self.mesh,
      **self._walk_attrs(xs), **self.span_attrs,
    ):
      outs, nonzero = self._compiled[sig](xs)
      jax.block_until_ready((outs, nonzero))
    with device_telemetry.transfer_span(
      "d2h", device_telemetry.nbytes_of(outs), kernel=self.name,
      mesh=self.mesh,
    ):
      return self._finish_call(outs, nonzero, k)

  def dispatch_staged(self, shape, dtype, fill):
    """``self(batch)`` on a host batch of ``planes`` arrays of ``shape``
    and ``dtype`` that ``fill(arrays, reused)`` writes first. The arrays
    come from this executor's free list (``reused`` True: their pages
    are already mapped, so the fill takes no page faults), or are
    allocated when none is free, so concurrent callers never share
    them. They go back on the list only after the dispatch has returned:
    the device then holds its own copy and the call's device arrays have
    been dropped (JAX's CPU backend may alias host memory in
    ``device_put``). A failed dispatch drops them. The outputs are fresh
    arrays, never staged."""
    key = (tuple(int(v) for v in shape), np.dtype(dtype).str)
    with self._staging_lock:
      free = self._staging.get(key)
      arrays = free.pop() if free else None
    reused = arrays is not None
    if not reused:
      arrays = tuple(np.empty(shape, dtype) for _ in range(self.planes))
    fill(arrays, reused)
    result = self(arrays if self.planes == 2 else arrays[0])
    with self._staging_lock:
      free = self._staging.get(key)
      if free is None:
        free = self._staging[key] = []
      free.append(arrays)
    return result

  def _finish_call(self, outs, nonzero, k):
    if self.planes == 2:
      result = [
        (np.asarray(ol)[:k], np.asarray(oh)[:k]) for ol, oh in outs
      ]
    else:
      result = [np.asarray(o)[:k] for o in outs]
    return result, int(nonzero)
