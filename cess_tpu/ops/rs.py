"""Reed-Solomon erasure coding as a streamed, mesh-sharded JAX data plane.

Two device kernels, both bit-identical to the numpy reference in
ops/gf256.py (tests/test_rs_hotpath.py pins every path against
`gf256.rs_encode_ref` / `rs_decode_ref`):

1. **bitplane** (MXU path, default on TPU): a GF(256) matrix-vector
   product is a GF(2)-linear map on the bit-planes of the data, so RS
   encode becomes a dense (8m x 8k) @ (8k x n) 0/1 int8 matmul reduced
   mod 2 — exactly the shape the TPU MXU is built for.

2. **gather** (default elsewhere): XOR-accumulated rows of the 256x256
   GF multiplication table.  On CPU hosts it avoids the 8× bit-plane
   blow-up and measures ~6× faster than bitplane at segment geometry.

Decode = encode with a host-computed k x k inverse (O(k^3) over tiny k,
cached per survivor mask; the O(k * n) byte work runs on device).

The data plane around the kernels (the part the north-star bench pays
for — RS-reconstructing 10 GiB is half the denominator):

* **One-shape tiled kernels** — streams process fixed-width `tile`
  slices of the byte axis (padded tail), so a multi-GiB stream at
  fixed (k, m, tile) traces each kernel exactly ONCE per process.
  `COMPILE_COUNTS` increments at trace time (same pattern as
  proof/fused.py) and the `rs_hotpath` CI gate asserts the invariant.
* **RSStream** — chunked transfer/compute overlap: the host packs and
  `device_put`s tile t+1 while tile t's matmul runs under JAX async
  dispatch, with buffer donation on the reconstruct path (TPU).
  The benchmark's repair cell, parallel/epoch_sim.py's RS stage, and
  the chain sim's upload/recovery helpers (chain/node.py) all drive it.
* **Mesh sharding in the core API** — `mesh=` on the batch calls
  shards the segment axis over a `jax.sharding.Mesh` via shard_map
  (embarrassingly parallel, no collectives); `RSStream.run` shards
  the byte axis of a single huge segment the same way.  The 8-device
  path is the same code tier-1 tests exercise on the virtual CPU mesh.
* **Grouped per-pattern recovery** — real networks lose *different*
  shards per segment; `reconstruct_batch` (and `RSStream.run_batch`)
  accept a per-segment survivor list, group segments by survivor mask
  (one host `mat_inv` per distinct mask), and run one batched matmul
  stream per group — bit-identical to the per-item numpy reference.
* **Stage histograms and spans** — streams observe the always-on
  `cess_rs_<stage>_seconds` histograms (rs_stage_registry, merged into
  node `system_metrics`) and open a `cess.rs.<stage>` profiler span
  over each, mirroring the proof pipelines; docs/perf.md explains how
  to read them.

Reference behavior being re-expressed: segment -> fragment erasure
coding with 1.5x redundancy (reference: runtime/src/lib.rs:1025,
file-bank/src/lib.rs:468) and the RS(12,4) / RS(2,1) geometries from
BASELINE.json configs.
"""

from __future__ import annotations

import os
import time as _time
from functools import lru_cache, reduce

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..utils.stages import StagePlane
from . import gf256

# Byte-axis tile width for streams (CESS_RS_TILE overrides).  1 MiB
# keeps the working set L2-resident on CPU hosts (the whole-array path
# materialised 8× bit-plane intermediates per pass) and amortises
# dispatch overhead on TPU; sub-tile arrays pad to a power of two so
# one-shot calls stay bounded at O(log n) compiles instead of one per
# distinct width.
TILE = int(os.environ.get("CESS_RS_TILE", str(1 << 20)))
# Segment-axis slab for batched streams (CESS_RS_SLAB overrides):
# every dispatched slab has exactly this many segments (padded tail),
# so grouped recovery reuses ONE executable across groups of any size.
SLAB = int(os.environ.get("CESS_RS_SLAB", "32"))
_MIN_WIDTH = 16  # floor of the pow2 bucket for tiny one-shot arrays

# Trace-time counters: jax re-traces only on a new argument-shape
# signature, so each count is the number of distinct compiled
# executables this process built for that kernel — the measurable form
# of the one-shape invariant (tests/test_rs_hotpath.py asserts a
# multi-tile stream traces its kernel exactly once).
COMPILE_COUNTS = {"bitplane": 0, "gather": 0}


# ------------------------------------------------------- stage telemetry
#
# Always-on per-stage histograms of the streamed data plane, the RS
# counterpart of proof/xla_backend.py's PROOF_STAGES, each also a
# profiler span `cess.rs.<stage>` over the same interval
# (utils/stages.py): `pack` is a tile's or slab's host work up to its
# upload, and holds `gather` (host copies: group gather, padded copy,
# contiguous copy) and `put` (`jax.device_put`); `matmul` the async
# kernel dispatches; `dispatch_wait` the final block on device results
# (the device time host packing failed to hide); `unpack` the
# device→host pulls and reassembly, holding `pull` (`np.asarray` of a
# device result) and `scatter` (concatenate, slice, scatter into the
# output).  Each slab dispatch counts its live segments and its slots.
# The registry is process-wide and merged into node `system_metrics`
# (node/rpc.py); CESS_STAGE_METRICS=0 switches it off for A/B
# measurement, same knob as the proof stages.

RS_STAGE_NAMES = ("pack", "gather", "put", "matmul", "dispatch_wait",
                  "unpack", "pull", "scatter")

RS_STAGES = StagePlane(
    "rs", RS_STAGE_NAMES, "cess_rs_{}_seconds", "RS stream {} stage time", {
        "bytes": ("cess_rs_bytes_total",
                  "payload bytes through streamed RS kernels"),
        "streams": ("cess_rs_streams_total", "RSStream passes executed"),
        "seconds": ("cess_rs_seconds_total",
                    "wall-clock seconds spent in RS streams"),
        "slab_segments": ("cess_rs_slab_segments_total",
                          "live segments in dispatched RSStream slabs"),
        "slab_slots": ("cess_rs_slab_slots_total",
                       "segment slots of dispatched RSStream slabs"),
    })


def rs_stage_registry():
    """The process-wide metrics registry for the RS data plane."""
    return RS_STAGES.registry()


# ------------------------------------------------- device-constant caches
#
# Module-level, keyed by code geometry: RSCode.__init__ used to
# re-upload the 64 KiB MUL_TABLE and re-expand/re-upload the parity
# bit-matrix on every construction — role clients building a code per
# file paid it per file.  Constructing RSCode(k, m) is now free after
# the first.


@lru_cache(maxsize=1)
def _mul_table_dev() -> jnp.ndarray:
    return jnp.asarray(gf256.MUL_TABLE)


@lru_cache(maxsize=64)
def _code_matrices(k: int, m: int) -> tuple[np.ndarray, np.ndarray]:
    """Host (parity, generator) for RS(k, m)."""
    return gf256.cauchy_matrix(k, m), gf256.encode_matrix(k, m)


@lru_cache(maxsize=64)
def _parity_dev(k: int, m: int) -> jnp.ndarray:
    return jnp.asarray(_code_matrices(k, m)[0])


@lru_cache(maxsize=64)
def _parity_bits_dev(k: int, m: int) -> jnp.ndarray:
    parity = _code_matrices(k, m)[0]
    return _bits_dev(parity.tobytes(), m, k)


@lru_cache(maxsize=64)
def _bit_matrix_cached(matrix_bytes: bytes, rows: int, cols: int) -> np.ndarray:
    m = np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    return gf256.bit_matrix(m)


@lru_cache(maxsize=256)
def _bits_dev(matrix_bytes: bytes, rows: int, cols: int) -> jnp.ndarray:
    """Device int8 upload of a GF(2)-expanded matrix (cached: recovery
    streams reuse one upload per survivor mask)."""
    return jnp.asarray(
        _bit_matrix_cached(matrix_bytes, rows, cols), dtype=jnp.int8
    )


@lru_cache(maxsize=256)
def _matrix_dev(matrix_bytes: bytes, rows: int, cols: int) -> jnp.ndarray:
    return jnp.asarray(
        np.frombuffer(matrix_bytes, dtype=np.uint8).reshape(rows, cols)
    )


@lru_cache(maxsize=4096)
def _inv_cached(k: int, m: int, present: tuple[int, ...]) -> np.ndarray:
    """Host k x k recovery inverse for one survivor mask (O(k^3) over
    tiny k — cached because grouped recovery hits few distinct masks)."""
    gen = _code_matrices(k, m)[1]
    return gf256.mat_inv(gen[np.asarray(present)])


# ---------------------------------------------------------------- helpers


def _bits_from_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """(r, n) uint8 -> (8r, n) int8 little-endian bit-planes."""
    r, n = x.shape
    shifts = jnp.arange(8, dtype=jnp.uint8)
    bits = (x[:, None, :] >> shifts[None, :, None]) & 1  # (r, 8, n)
    return bits.reshape(8 * r, n).astype(jnp.int8)


def _bytes_from_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """(8r, n) int -> (r, n) uint8, little-endian bit order."""
    r8, n = bits.shape
    r = r8 // 8
    b = bits.reshape(r, 8, n).astype(jnp.uint8)
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))[None, :, None]
    return jnp.sum(b * weights, axis=1, dtype=jnp.uint8)


def _pow2(n: int) -> int:
    return 1 << max(0, (n - 1).bit_length())


# ---------------------------------------------------------------- kernels


def _matmul_gf_bitplane(bitmat: jnp.ndarray, data: jnp.ndarray):
    """GF(256) matrix product via mod-2 int8 matmul.

    bitmat: (8m, 8k) int8 0/1 — host-expanded GF(2) matrix
    data:   (k, n) uint8
    returns (m, n) uint8
    """
    COMPILE_COUNTS["bitplane"] += 1  # trace-time: one per compiled shape
    bits = _bits_from_bytes(data)  # (8k, n) int8
    acc = jax.lax.dot_general(
        bitmat,
        bits,
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )  # (8m, n) int32, each entry <= 8k < 2^31
    return _bytes_from_bits(acc & 1)


def _matmul_gf_gather(matrix: jnp.ndarray, data: jnp.ndarray, mul_table: jnp.ndarray):
    """GF(256) matrix product via MUL_TABLE row gathers.

    matrix: (m, k) uint8, data: (k, n) uint8 -> (m, n) uint8
    """
    COMPILE_COUNTS["gather"] += 1  # trace-time: one per compiled shape
    k = data.shape[0]

    def one_row(row):  # row: (k,) uint8
        terms = [mul_table[row[i], :][data[i]] for i in range(k)]
        return reduce(jnp.bitwise_xor, terms)

    return jax.vmap(one_row)(matrix)


def _donate_ok() -> bool:
    """Buffer donation only helps (and only stays warning-free) on TPU;
    CPU/GPU emulation paths run the plain kernels."""
    return jax.default_backend() == "tpu"


@lru_cache(maxsize=8)
def _kernel_jit(path: str, donate: bool):
    """Module-cached jitted kernel.  `donate` hands the data buffer to
    XLA for output reuse — valid when in/out shapes match (the k -> k
    reconstruct path), a free HBM saving on GiB streams."""
    fn = _matmul_gf_bitplane if path == "bitplane" else _matmul_gf_gather
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


@lru_cache(maxsize=8)
def _batch_kernel_jit(path: str, donate: bool):
    if path == "bitplane":
        fn = jax.vmap(_matmul_gf_bitplane, in_axes=(None, 0))
    else:
        fn = jax.vmap(_matmul_gf_gather, in_axes=(None, 0, None))
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


@lru_cache(maxsize=32)
def _sharded_batch_fn(mesh, path: str):
    """Batch-axis mesh sharding: segments split over devices, no
    collectives (folds parallel/epoch_sim's former _rs_recover_sharded
    into the core API).  Cached per (mesh, path) — rebuilding the jit
    wrapper per call would re-trace every call."""
    axis = mesh.axis_names[0]
    if path == "bitplane":
        fn = jax.shard_map(
            jax.vmap(_matmul_gf_bitplane, in_axes=(None, 0)),
            mesh=mesh,
            in_specs=(P(None, None), P(axis, None, None)),
            out_specs=P(axis, None, None),
            check_vma=False,
        )
    else:
        fn = jax.shard_map(
            jax.vmap(_matmul_gf_gather, in_axes=(None, 0, None)),
            mesh=mesh,
            in_specs=(
                P(None, None), P(axis, None, None), P(None, None)
            ),
            out_specs=P(axis, None, None),
            check_vma=False,
        )
    return jax.jit(fn)


@lru_cache(maxsize=32)
def _sharded_cols_fn(mesh, path: str):
    """Byte-axis mesh sharding: one huge segment's columns split over
    devices (the single-giant-file recovery shape)."""
    axis = mesh.axis_names[0]
    if path == "bitplane":
        fn = jax.shard_map(
            _matmul_gf_bitplane,
            mesh=mesh,
            in_specs=(P(None, None), P(None, axis)),
            out_specs=P(None, axis),
            check_vma=False,
        )
    else:
        fn = jax.shard_map(
            _matmul_gf_gather,
            mesh=mesh,
            in_specs=(P(None, None), P(None, axis), P(None, None)),
            out_specs=P(None, axis),
            check_vma=False,
        )
    return jax.jit(fn)


def default_path() -> str:
    """bitplane rides the MXU on TPU; the gather kernel avoids the 8×
    bit-plane memory blow-up everywhere else (measured ~6× faster at
    segment geometry on CPU hosts — BENCH_r07)."""
    return "bitplane" if jax.default_backend() == "tpu" else "gather"


# ------------------------------------------------------------- validation


def check_present(present, k: int, m: int) -> tuple[int, ...]:
    """Validate one survivor list and return the k-row prefix actually
    consumed.  Duplicate or out-of-range indices used to surface as a
    late 'singular GF(256) matrix' (or silently selected wrong rows);
    they are a caller bug and fail loudly up front."""
    idx = [int(i) for i in present]
    if len(idx) < k:
        raise ValueError(f"need {k} shards to recover, have {len(idx)}")
    idx = idx[:k]
    for i in idx:
        if not 0 <= i < k + m:
            raise ValueError(
                f"survivor index {i} out of range for RS({k},{m}) "
                f"(valid: 0..{k + m - 1})"
            )
    if len(set(idx)) != k:
        raise ValueError(f"duplicate survivor indices in {idx}")
    return tuple(idx)


def _is_per_segment(present) -> bool:
    """True when `present` is a per-segment list of survivor lists."""
    if isinstance(present, np.ndarray):
        return present.ndim == 2
    return bool(len(present)) and not np.isscalar(present[0]) and not isinstance(
        present[0], (int, np.integer)
    )


def _check_shards(a, min_rows: int, batched: bool) -> None:
    shape = getattr(a, "shape", None)
    want = 3 if batched else 2
    if shape is None or len(shape) != want:
        raise ValueError(
            f"shard array must be {want}-D "
            f"{'(B, rows, n)' if batched else '(rows, n)'}, got shape {shape}"
        )
    if 0 in shape:
        raise ValueError(f"empty shard array (shape {shape})")
    rows = shape[1] if batched else shape[0]
    if rows < min_rows:
        raise ValueError(f"need {min_rows} shard rows, have {rows}")


# ---------------------------------------------------------------- public API


class RSCode:
    """Systematic RS(k, m) over GF(2^8) with Cauchy parity rows.

    encode: (k, n) data shards -> (m, n) parity shards
    reconstruct: any k of the k+m shards -> original k data shards
    Batched variants take a leading segment axis and an optional
    `mesh=` to shard it (BASELINE configs 2 and 5); `present` on the
    batch form may be one shared survivor list or one list per segment
    (grouped per-pattern recovery).  GiB-scale host arrays stream
    through RSStream.

    path: "bitplane" (MXU matmul), "gather" (table gathers), or "auto"
    (bitplane on TPU, gather elsewhere).  All paths are bit-identical.
    """

    def __init__(
        self, k: int, m: int, path: str = "bitplane",
        tile: int | None = None,
    ) -> None:
        if path == "auto":
            path = default_path()
        if path not in ("bitplane", "gather"):
            raise ValueError(f"unknown RS path {path!r}")
        if k < 1 or m < 1:
            raise ValueError(f"RS(k={k}, m={m}) needs k >= 1 and m >= 1")
        if k + m > gf256.FIELD:
            raise ValueError("k + m must be <= 256")
        self.k, self.m, self.path = k, m, path
        self.tile = int(tile) if tile else TILE
        self._parity, self._gen = _code_matrices(k, m)
        self._mul_table = _mul_table_dev()
        self._parity_dev = _parity_dev(k, m)
        self._parity_bits = _parity_bits_dev(k, m)

    # -- kernel dispatch ------------------------------------------------

    def _mat_dev(self, mat_host: np.ndarray) -> jnp.ndarray:
        """Device form of a host GF(256) matrix for this code's path."""
        raw = np.ascontiguousarray(mat_host)
        r, c = raw.shape
        if self.path == "bitplane":
            return _bits_dev(raw.tobytes(), r, c)
        return _matrix_dev(raw.tobytes(), r, c)

    def _kernel(self, mat_dev, data, *, donate: bool = False):
        don = donate and _donate_ok()
        if self.path == "bitplane":
            return _kernel_jit("bitplane", don)(mat_dev, data)
        return _kernel_jit("gather", don)(mat_dev, data, self._mul_table)

    def _batch_kernel(self, mat_dev, data, mesh=None, *, donate=False):
        if mesh is not None:
            fn = _sharded_batch_fn(mesh, self.path)
            if self.path == "bitplane":
                return fn(mat_dev, data)
            return fn(mat_dev, data, self._mul_table)
        don = donate and _donate_ok()
        if self.path == "bitplane":
            return _batch_kernel_jit("bitplane", don)(mat_dev, data)
        return _batch_kernel_jit("gather", don)(
            mat_dev, data, self._mul_table
        )

    def _apply(self, mat_host: np.ndarray, data, mesh=None):
        """mat @ data over the byte axis with one-shape padding: widths
        below `tile` bucket to a power of two (bounded compiles);
        wider arrays run fixed `tile` slices via the batched kernel."""
        mat_dev = self._mat_dev(mat_host)
        n = data.shape[-1]
        if mesh is not None:
            n_dev = mesh.devices.size
            pad_n = -n % n_dev
            xp = jnp.asarray(data, jnp.uint8)
            if pad_n:
                xp = jnp.pad(xp, [(0, 0), (0, pad_n)])
            fn = _sharded_cols_fn(mesh, self.path)
            out = (
                fn(mat_dev, xp)
                if self.path == "bitplane"
                else fn(mat_dev, xp, self._mul_table)
            )
            return out[..., :n] if pad_n else out
        tile = self.tile
        if n > tile:
            tiles = -(-n // tile)
            xp = jnp.pad(
                jnp.asarray(data, jnp.uint8), [(0, 0), (0, tiles * tile - n)]
            )
            stacked = jnp.moveaxis(
                xp.reshape(data.shape[0], tiles, tile), 1, 0
            )  # (tiles, rows, tile)
            out = self._batch_kernel(mat_dev, stacked)
            out = jnp.moveaxis(out, 0, 1).reshape(mat_host.shape[0], -1)
            return out[..., :n]
        width = max(_pow2(n), _MIN_WIDTH)
        if width != n:
            xp = jnp.pad(jnp.asarray(data, jnp.uint8), [(0, 0), (0, width - n)])
            return self._kernel(mat_dev, xp)[..., :n]
        return self._kernel(mat_dev, jnp.asarray(data, jnp.uint8))

    def _apply_batch(self, mat_host: np.ndarray, data, mesh=None):
        """Batched mat @ data with the segment axis pow2-bucketed (and
        rounded to the mesh size when sharded)."""
        mat_dev = self._mat_dev(mat_host)
        b = data.shape[0]
        bp = max(_pow2(b), 1)
        if mesh is not None:
            n_dev = mesh.devices.size
            bp = -(-bp // n_dev) * n_dev
        xp = jnp.asarray(data, jnp.uint8)
        if bp != b:
            xp = jnp.pad(xp, [(0, bp - b), (0, 0), (0, 0)])
        out = self._batch_kernel(mat_dev, xp, mesh=mesh)
        return out[:b] if bp != b else out

    # -- encode ---------------------------------------------------------

    def encode(self, data, mesh=None) -> jnp.ndarray:
        """(k, n) uint8 -> (m, n) uint8 parity.  `mesh` shards the byte
        axis over devices (single huge segment)."""
        _check_shards(data, self.k, batched=False)
        return self._apply(self._parity, data, mesh=mesh)

    def encode_batch(self, data, mesh=None) -> jnp.ndarray:
        """(b, k, n) -> (b, m, n).  `mesh` shards the segment axis."""
        _check_shards(data, self.k, batched=True)
        return self._apply_batch(self._parity, data, mesh=mesh)

    # -- decode ---------------------------------------------------------

    def recovery_matrix(self, present) -> np.ndarray:
        """Host-side k x k inverse for the surviving shard set (indices
        validated; cached per distinct mask)."""
        return _inv_cached(
            self.k, self.m, check_present(present, self.k, self.m)
        ).copy()

    def reconstruct(self, shards, present, mesh=None) -> jnp.ndarray:
        """shards (>=k, n) rows matching `present` global indices ->
        (k, n) data.  `mesh` shards the byte axis."""
        _check_shards(shards, self.k, batched=False)
        mask = check_present(present, self.k, self.m)
        inv = _inv_cached(self.k, self.m, mask)
        return self._apply(inv, jnp.asarray(shards)[: self.k], mesh=mesh)

    def reconstruct_batch(self, shards, present, mesh=None):
        """(b, >=k, n) -> (b, k, n).

        `present` is either ONE survivor list shared by every segment,
        or a per-segment list of survivor lists — segments are then
        grouped by survivor mask (one host inverse per distinct mask,
        one batched matmul per group; returns host uint8, assembled in
        segment order, bit-identical to per-item gf256.rs_decode_ref).
        """
        _check_shards(shards, self.k, batched=True)
        if _is_per_segment(present):
            return RSStream(self, present=present, mesh=mesh).run_batch(
                np.asarray(shards, dtype=np.uint8)
            )
        mask = check_present(present, self.k, self.m)
        inv = _inv_cached(self.k, self.m, mask)
        return self._apply_batch(
            inv, jnp.asarray(shards)[:, : self.k], mesh=mesh
        )


# ---------------------------------------------------------------- streams


def _host_tile(part: np.ndarray, shape: tuple[int, ...]) -> np.ndarray:
    """`part` zero-padded at the end of each axis to `shape` (the padded
    tail keeps one kernel shape), contiguous for `device_put`."""
    if part.shape != shape:
        padded = np.zeros(shape, dtype=np.uint8)
        padded[tuple(slice(0, d) for d in part.shape)] = part
        part = padded
    return np.ascontiguousarray(part)


class RSStream:
    """Streamed RS over GiB-scale host arrays with transfer/compute
    overlap.

    The host packs (slices, pads, `device_put`s) tile t+1 while tile
    t's matmul executes under JAX async dispatch — nothing blocks on
    device values until every tile is in flight, then one
    block_until_ready drains the pipeline and the outputs are pulled.
    `present=None` streams encode; a survivor list (or per-segment
    lists for `run_batch`) streams reconstruction, with buffer
    donation on TPU (in/out shapes match on the k -> k decode).

    Stage seconds land in the always-on cess_rs_* histograms, each
    under a `cess.rs.<stage>` profiler span (RS_STAGES; docs/perf.md).
    """

    def __init__(
        self, code: RSCode, *, present=None, mesh=None,
        tile: int | None = None, slab: int | None = None,
    ) -> None:
        self.code = code
        self.mesh = mesh
        self.tile = int(tile) if tile else code.tile
        slab = int(slab) if slab else SLAB
        if mesh is not None:
            # shard_map splits the tile / slab axis over devices, so
            # both must divide the mesh size
            n_dev = mesh.devices.size
            slab = -(-slab // n_dev) * n_dev
            self.tile = -(-self.tile // n_dev) * n_dev
        self.slab = slab
        self.present = present
        if present is not None and not _is_per_segment(present):
            # validate the shared mask once, up front
            check_present(present, code.k, code.m)

    # -- telemetry ------------------------------------------------------

    @staticmethod
    def _account(nbytes: int, t_start: float) -> None:
        RS_STAGES.count(bytes=nbytes, streams=1,
                        seconds=_time.perf_counter() - t_start)

    # -- byte-axis stream ----------------------------------------------

    def _op_matrix(self) -> np.ndarray:
        code = self.code
        if self.present is None:
            return code._parity
        return _inv_cached(
            code.k, code.m, check_present(self.present, code.k, code.m)
        )

    def run(self, data: np.ndarray) -> np.ndarray:
        """(rows, n) host uint8 stream -> (out_rows, n) host uint8.

        rows = k for encode; the first k survivor rows (matching
        `present`) for reconstruct.  The byte axis is processed in
        fixed `tile` slices (padded tail) — ONE kernel shape per
        stream, asserted by COMPILE_COUNTS.
        """
        code = self.code
        stage = RS_STAGES.stage
        t_start = _time.perf_counter()
        _check_shards(data, code.k, batched=False)
        if self.present is None and data.shape[0] != code.k:
            raise ValueError(
                f"encode stream needs exactly {code.k} data rows, "
                f"got {data.shape[0]}"
            )
        data = np.asarray(data, dtype=np.uint8)[: code.k]
        mat_dev = code._mat_dev(self._op_matrix())
        n = data.shape[1]
        tile = self.tile
        donate = self.present is not None
        outs = []
        pulled = []
        for off in range(0, n, tile):
            with stage("pack"):
                with stage("gather"):
                    host = _host_tile(data[:, off : off + tile],
                                      (code.k, tile))
                with stage("put"):
                    dev = jax.device_put(host)
            with stage("matmul"):
                if self.mesh is not None:
                    fn = _sharded_cols_fn(self.mesh, code.path)
                    out = (
                        fn(mat_dev, dev)
                        if code.path == "bitplane"
                        else fn(mat_dev, dev, code._mul_table)
                    )
                else:
                    out = code._kernel(mat_dev, dev, donate=donate)
                outs.append(out)
            if len(outs) > 1:
                # pull tile t-1's result under tile t's compute: the
                # device→host copy of an already-finished tile overlaps
                # the in-flight matmul instead of queueing serially
                # behind the final drain.  The pull still counts as
                # `unpack` — overlapped or not, it is device→host
                # reassembly time (keeps the stage histogram honest).
                with stage("unpack"), stage("pull"):
                    # cesslint: allow[host-sync] pulls the PREVIOUS
                    # tile, already computed, while tile t is in flight
                    pulled.append(np.asarray(outs[-2]))
                    outs[-2] = None
        with stage("dispatch_wait"):
            jax.block_until_ready(outs[-1])
        with stage("unpack"):
            with stage("pull"):
                pulled.append(np.asarray(outs[-1]))
            with stage("scatter"):
                res = np.concatenate(pulled, axis=1)[:, :n]
        self._account(data.nbytes, t_start)
        return res

    # -- segment-axis stream -------------------------------------------

    def _patterns(self, b: int) -> list[tuple[int, ...]]:
        code = self.code
        if not _is_per_segment(self.present):
            mask = check_present(self.present, code.k, code.m)
            return [mask] * b
        pats = [
            check_present(p, code.k, code.m) for p in self.present
        ]
        if len(pats) != b:
            raise ValueError(
                f"{len(pats)} survivor lists for {b} segments"
            )
        return pats

    def _stream_slabs(self, mat: np.ndarray, batch: np.ndarray, out, idx):
        """Gather one group's segments out of `batch` (`idx`: the
        group's row indices, or slice(None) for every row), dispatch
        them in fixed-size slabs, and scatter results into `out` rows
        `idx`."""
        code = self.code
        stage = RS_STAGES.stage
        mat_dev = code._mat_dev(mat)
        slab = self.slab
        b = batch.shape[0] if isinstance(idx, slice) else len(idx)
        group = None
        outs = []
        pulled = []
        for off in range(0, b, slab):
            with stage("pack"):
                with stage("gather"):
                    if group is None:
                        group = batch[idx, : code.k]  # group gather
                    host = _host_tile(group[off : off + slab],
                                      (slab,) + group.shape[1:])
                with stage("put"):
                    dev = jax.device_put(host)
            with stage("matmul"):
                outs.append(
                    code._batch_kernel(
                        mat_dev, dev, mesh=self.mesh,
                        donate=self.present is not None,
                    )
                )
                RS_STAGES.count(slab_segments=min(slab, b - off),
                                slab_slots=slab)
            if len(outs) > 1:
                # pull slab t-1's result under slab t's compute (see
                # RSStream.run): overlapped device→host copies still
                # accrue to `unpack`
                with stage("unpack"), stage("pull"):
                    # cesslint: allow[host-sync] pulls the PREVIOUS
                    # slab, already computed, while slab t is in flight
                    pulled.append(np.asarray(outs[-2]))
                    outs[-2] = None
        with stage("dispatch_wait"):
            jax.block_until_ready(outs[-1])
        with stage("unpack"):
            with stage("pull"):
                pulled.append(np.asarray(outs[-1]))
            with stage("scatter"):
                out[idx] = np.concatenate(pulled, axis=0)[:b]

    def run_batch(self, batch: np.ndarray) -> np.ndarray:
        """(B, rows, n) host segments -> (B, out_rows, n) host uint8.

        Encode (`present=None`): rows = k, out_rows = m.  Reconstruct:
        per-segment survivor rows; segments sharing a survivor mask are
        grouped into one batched matmul stream each (grouped
        per-pattern recovery), every dispatch a fixed (slab, k, n)
        shape so ALL groups share one executable.
        """
        code = self.code
        t_start = _time.perf_counter()
        _check_shards(batch, code.k, batched=True)
        batch = np.asarray(batch, dtype=np.uint8)
        b, _, n = batch.shape
        if self.present is None:
            if batch.shape[1] != code.k:
                raise ValueError(
                    f"encode stream needs exactly {code.k} data rows, "
                    f"got {batch.shape[1]}"
                )
            out = np.empty((b, code.m, n), dtype=np.uint8)
            self._stream_slabs(code._parity, batch, out, slice(None))
            self._account(batch.nbytes, t_start)
            return out
        pats = self._patterns(b)
        out = np.empty((b, code.k, n), dtype=np.uint8)
        groups: dict[tuple[int, ...], list[int]] = {}
        for i, p in enumerate(pats):
            groups.setdefault(p, []).append(i)
        for mask, idx in groups.items():
            inv = _inv_cached(code.k, code.m, mask)
            # cesslint: allow[host-sync] np.asarray on a host-side
            # python index list (group gather rows), not a device value
            self._stream_slabs(inv, batch, out, np.asarray(idx))
        self._account(batch.nbytes, t_start)
        return out


# Protocol geometry (reference: primitives/common/src/lib.rs:60-62 — 16 MiB
# segments, 8 MiB fragments, i.e. k=2 data + m=1 parity).
SEGMENT_K = 2
SEGMENT_M = 1


def segment_code(path: str = "auto", tile: int | None = None) -> RSCode:
    return RSCode(SEGMENT_K, SEGMENT_M, path=path, tile=tile)
