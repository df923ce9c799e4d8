"""Historical embedding storage (the paper's central data structure).

One table per hidden layer: `H̄^(ℓ) ∈ R^{N×d}` holding the layer-ℓ output of
every node from the last time it was in a mini-batch. `pull` gathers rows for
out-of-batch (halo) neighbors; `push` scatters freshly computed in-batch
rows back. Both are pure functions (tables are carried through the jitted
train step and donated), which is the TPU-native analogue of PyGAS's pinned
CPU buffers + CUDA-stream transfers: XLA schedules the gather/dynamic-update
asynchronously with layer compute.

An optional staleness clock (`age`) is kept for the error-bound metrics
(Lemma 1 / Theorem 2 validation), not used by training itself.

`pull`/`push` here are the pure-jnp reference implementations; the training
hot path goes through `kernels.ops.pull_rows`/`push_rows`, which dispatch
between these semantics and the Pallas gather/scatter kernels per backend.

`HistoryStore` is the typed runtime handle over the same state: the
resolved kernel backend is bound ONCE at construction (aux data on the
pytree, so it cannot silently change between jitted calls), and all
history I/O goes through its `pull`/`push`/`tick`/`bytes` methods instead
of free functions plus per-call `backend=` threading.

Compression (`history_dtype ∈ {"f32", "bf16", "int8", "vq"}`, also aux
data, one registry entry each — see `HistoryCodec`/`get_codec`):
histories are *already* approximate (the paper's Lemma 3.1 / Theorem 3.2
bound the staleness error), so storing them below f32 trades a small,
measurable extra error for a 2x/~4x cut of the dominant GPU/TPU-memory
term — the [N+1, d] tables. ``bf16`` truncates mantissas in place;
``int8`` stores symmetric per-row quantized rows next to a per-row f32
scale table (`scales`): push computes `s_i = max|v_i| / 127` and scatters
`round(v_i / s_i)`; pull (and the fused dequant-gather kernels in
`kernels/gather.py` / `kernels/fused.py`) reconstruct `q_i * s_i` without
ever materializing an f32 copy of the table in HBM. The added per-element
error is bounded by `s_i / 2 = max|v_i| / 254` — see `quantization_error`,
surfaced as the `hist_quant_err` training diagnostic next to
`halo_age_*`. ``vq`` product-quantizes each row: VQ_SUBDIM-wide
subvectors become uint8 indices into a per-layer k-means codebook
(`codebooks`, refit at an epoch cadence from push statistics), next to
the same per-row f32 scale — ~20-25x fewer table bytes than f32, with
the codebook lookup fused into the gather kernels exactly like the int8
dequant.
"""
from __future__ import annotations

import functools
import os
from dataclasses import dataclass, field, replace
from typing import Any, Callable, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HISTORY_STORAGES = ("device", "host")

# Product-quantization (history_dtype="vq") constants: each row is split
# into d / VQ_SUBDIM subvectors, each encoded as one uint8 index into a
# per-layer [S, VQ_CODES, VQ_SUBDIM] f32 codebook.
VQ_SUBDIM = 8
VQ_CODES = 256
VQ_SEED = 0


# ---------------------------------------------------------------------------
# History-dtype registry. ONE table drives every dtype decision in the
# repo (storage dtype, table width, aux allocation, quantize/roundtrip):
# adding a dtype is one `_CODECS` entry, and every entry point rejects
# unknown names with the SAME ValueError (via `get_codec`).
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class HistoryCodec:
    """One row of the history-dtype registry.

    `lossless` — push/pull round-trips bit-exact (quant error is 0).
    `scaled` — a per-row f32 scale table rides next to each layer table.
    `vq` — a per-layer codebook (plus k-means refit stats) rides along,
    and the layer table holds uint8 codes of width d / VQ_SUBDIM instead
    of d feature elements.
    `encode(values, codebook)` -> (table_rows, scales) in storage
    precision; `roundtrip(values, codebook)` -> f32 reconstruction (what
    a push-then-pull returns) — the single definition both backends and
    `quantization_error` share.
    """
    name: str
    storage: Any
    lossless: bool
    scaled: bool
    vq: bool
    encode: Optional[Callable] = None
    roundtrip: Callable = field(default=lambda v, cb: v)

    def table_width(self, d: int) -> int:
        return vq_table_width(d) if self.vq else d


def _roundtrip_bf16(v, cb):
    return v.astype(jnp.bfloat16).astype(jnp.float32)


def _encode_int8(v, cb):
    return quantize_rows(v)


def _roundtrip_int8(v, cb):
    return dequantize_rows(*quantize_rows(v))


def _encode_vq(v, cb):
    return vq_encode_rows(v, cb)


def _roundtrip_vq(v, cb):
    codes, scales = vq_encode_rows(v, cb)
    return vq_decode_rows(codes, cb, scales)


_CODECS = {
    "f32": HistoryCodec("f32", jnp.float32, lossless=True, scaled=False,
                        vq=False),
    "bf16": HistoryCodec("bf16", jnp.bfloat16, lossless=False,
                         scaled=False, vq=False,
                         roundtrip=_roundtrip_bf16),
    "int8": HistoryCodec("int8", jnp.int8, lossless=False, scaled=True,
                         vq=False, encode=_encode_int8,
                         roundtrip=_roundtrip_int8),
    "vq": HistoryCodec("vq", jnp.uint8, lossless=False, scaled=True,
                       vq=True, encode=_encode_vq,
                       roundtrip=_roundtrip_vq),
}

HISTORY_DTYPES = tuple(_CODECS)


def get_codec(history_dtype: str) -> HistoryCodec:
    """Registry lookup; THE canonical unknown-dtype error (every entry
    point — resolve, storage_dtype, create, quantization_error, bench
    and serve call sites — funnels through here)."""
    codec = _CODECS.get(history_dtype)
    if codec is None:
        raise ValueError(
            f"history_dtype must be one of {HISTORY_DTYPES}, "
            f"got {history_dtype}")
    return codec


def resolve_history_dtype(history_dtype: Optional[str] = None) -> str:
    """arg > $REPRO_HISTORY_DTYPE > "f32" (mirrors
    `kernels.ops.resolve_backend`)."""
    for cand in (history_dtype,
                 os.environ.get("REPRO_HISTORY_DTYPE") or None):
        if cand is not None:
            get_codec(cand)
            return cand
    return "f32"


def storage_dtype(history_dtype: str):
    """The on-table element dtype for a resolved history_dtype."""
    return get_codec(history_dtype).storage


def resolve_history_storage(storage: Optional[str] = None) -> str:
    """arg > $REPRO_HISTORY_STORAGE > "device". ``"host"`` pins the
    history tables in host RAM (the paper keeps H̄ on CPU RAM for its
    100M-node runs) and streams pulled rows device-ward — table capacity
    then scales with CPU RAM instead of HBM."""
    for cand in (storage,
                 os.environ.get("REPRO_HISTORY_STORAGE") or None):
        if cand is not None:
            if cand not in HISTORY_STORAGES:
                raise ValueError(
                    f"storage must be one of {HISTORY_STORAGES}, "
                    f"got {cand}")
            return cand
    return "device"


@functools.lru_cache(maxsize=1)
def _memory_kinds() -> Tuple[Optional[str], Optional[str]]:
    """(host_kind, device_kind) for the default device, or (None, None)
    when the runtime has no addressable-memory API (any other error
    propagates). On TPU this is ("pinned_host", "device")."""
    dev = jax.devices()[0]
    try:
        kinds = {m.kind for m in dev.addressable_memories()}
        default = dev.default_memory().kind
    except (AttributeError, NotImplementedError):
        return None, None
    host = next((k for k in ("pinned_host", "unpinned_host")
                 if k in kinds), None)
    return host, default


def host_storage_supported() -> bool:
    """True when the runtime can pin arrays in a host memory kind."""
    return _memory_kinds()[0] is not None


def _put_kind(arrays: Tuple[jnp.ndarray, ...], kind: Optional[str]
              ) -> Tuple[jnp.ndarray, ...]:
    if kind is None:
        return tuple(arrays)
    sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0],
                                                 memory_kind=kind)
    return tuple(jax.device_put(a, sharding) for a in arrays)


# ---------------------------------------------------------------------------
# Symmetric per-row int8 quantization (pure jnp; the kernels fuse the
# dequant side into their gathers, see kernels/gather.py / fused.py)
# ---------------------------------------------------------------------------

def row_scales(values: jnp.ndarray) -> jnp.ndarray:
    """Symmetric per-row scale `s_i = max|v_i| / 127` (1.0 for all-zero
    rows so the dequant stays finite). THE definition of the scale
    formula — `quantize_rows` and the kernel push path
    (`kernels.ops.push_rows_q`) both call this, so the jnp and kernel
    backends cannot drift apart on it."""
    amax = jnp.max(jnp.abs(values.astype(jnp.float32)), axis=-1)
    return jnp.where(amax > 0, amax / 127.0, 1.0)


def quantize_rows(values: jnp.ndarray
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """values [M, d] -> (q int8 [M, d], scales f32 [M]).

    Symmetric per-row quantization: `s_i = row_scales(v)_i`, `q_i =
    round(v_i / s_i)` clipped to [-127, 127]. Per-element error <=
    s_i / 2. The round/clip half is mirrored in-kernel by
    `kernels.scatter._quantize` (it cannot be shared across the
    pallas_call boundary) — keep the two in lockstep."""
    v = values.astype(jnp.float32)
    scales = row_scales(v)
    q = jnp.clip(jnp.round(v / scales[:, None]), -127, 127)
    return q.astype(jnp.int8), scales


def dequantize_rows(q: jnp.ndarray, scales: jnp.ndarray) -> jnp.ndarray:
    """(q int8 [M, d], scales f32 [M]) -> f32 [M, d]."""
    return q.astype(jnp.float32) * scales[:, None]


# ---------------------------------------------------------------------------
# Product quantization (history_dtype="vq"): per-layer codebook
# [S, VQ_CODES, VQ_SUBDIM] f32, codes uint8 [N+1, S], per-row f32 scale.
# Encode normalizes each row by max|v| and snaps every VQ_SUBDIM-wide
# subvector to its nearest codebook entry; decode is a pure gather + one
# scale multiply, which is what rides the fused kernels' VPU lane. All
# helpers here are THE shared definitions — the jnp backend calls them
# directly and the Pallas kernels mirror them op-for-op, so the bitwise
# tests hold.
# ---------------------------------------------------------------------------

def vq_table_width(d: int) -> int:
    """Codes-table width S for a d-wide layer. vq requires
    d % VQ_SUBDIM == 0 so S * VQ_SUBDIM == d exactly (every consumer can
    then recover d from the codebook shape alone)."""
    if d % VQ_SUBDIM:
        raise ValueError(
            f"history_dtype='vq' requires feature dims divisible by "
            f"{VQ_SUBDIM}, got {d}")
    return d // VQ_SUBDIM


def vq_init_codebook(d: int, seed: int = VQ_SEED) -> jnp.ndarray:
    """Deterministic initial codebook [S, VQ_CODES, VQ_SUBDIM] f32:
    uniform in [-1, 1] (rows are max-abs normalized before encoding, so
    that covers the whole range), with entry 0 pinned to the zero vector
    so all-zero rows — the initial table state — round-trip exactly.
    `vq_refit_codebook` keeps the pin."""
    s = vq_table_width(d)
    cb = jax.random.uniform(jax.random.PRNGKey(seed),
                            (s, VQ_CODES, VQ_SUBDIM), jnp.float32,
                            -1.0, 1.0)
    return cb.at[:, 0, :].set(0.0)


def vq_row_scales(values: jnp.ndarray) -> jnp.ndarray:
    """Per-row normalizer `s_i = max|v_i|` (1.0 for all-zero rows). The
    vq analogue of `row_scales` — codebook entries live in [-1, 1]^ds,
    so rows are brought there before the nearest-entry search."""
    amax = jnp.max(jnp.abs(values.astype(jnp.float32)), axis=-1)
    return jnp.where(amax > 0, amax, 1.0)


def vq_encode_rows(values: jnp.ndarray, codebook: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """values [M, d] -> (codes uint8 [M, S], scales f32 [M]): per
    subvector s, the index of the codebook entry nearest (L2) to the
    normalized subvector. Mirrored in-kernel by
    `kernels.scatter._vq_kernel` — keep the two in lockstep."""
    v = values.astype(jnp.float32)
    scales = vq_row_scales(v)
    s_, _, ds = codebook.shape
    u = (v / scales[:, None]).reshape(v.shape[0], s_, 1, ds)
    d2 = jnp.sum(jnp.square(u - codebook[None]), axis=-1)  # [M, S, C]
    return jnp.argmin(d2, axis=-1).astype(jnp.uint8), scales


def vq_decode_rows(codes: jnp.ndarray, codebook: jnp.ndarray,
                   scales: jnp.ndarray) -> jnp.ndarray:
    """(codes uint8 [M, S], codebook [S, C, ds], scales f32 [M]) ->
    f32 [M, S*ds]. A pure selection + one multiply: the kernels realize
    the same selection as a one-hot matmul (bit-identical — every output
    element is exactly one codebook element times 1.0 plus exact
    zeros)."""
    s_, _, ds = codebook.shape
    rec = codebook[jnp.arange(s_)[None, :], codes.astype(jnp.int32)]
    return rec.reshape(codes.shape[0], s_ * ds) * \
        scales[:, None].astype(jnp.float32)


def vq_accumulate_stats(codes: jnp.ndarray, values: jnp.ndarray,
                        scales: jnp.ndarray, mask: jnp.ndarray,
                        counts: jnp.ndarray, sums: jnp.ndarray
                        ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Fold one push's assignments into the running k-means sufficient
    statistics (counts [S, C], sums [S, C, ds]): the E-step happens for
    free at encode time; `vq_refit_codebook` applies the M-step at the
    configured epoch cadence. Masked (padding) rows contribute
    nothing."""
    s_, c = counts.shape
    v = values.astype(jnp.float32)
    u = (v / scales[:, None]).reshape(v.shape[0], s_, -1)
    onehot = (codes[:, :, None].astype(jnp.int32)
              == jnp.arange(c)[None, None, :]).astype(jnp.float32)
    onehot = onehot * mask.astype(jnp.float32)[:, None, None]
    return (counts + jnp.sum(onehot, axis=0),
            sums + jnp.einsum("msc,msd->scd", onehot, u))


def vq_refit_codebook(codebook: jnp.ndarray, counts: jnp.ndarray,
                      sums: jnp.ndarray) -> jnp.ndarray:
    """k-means M-step over the accumulated push statistics: centroids
    with assignments move to the mean of their assigned normalized
    subvectors, empty ones stay put, entry 0 stays pinned at zero."""
    hit = (counts > 0)[:, :, None]
    new = jnp.where(hit, sums / jnp.maximum(counts, 1.0)[:, :, None],
                    codebook)
    return new.at[:, 0, :].set(0.0)


def quantization_error(values: jnp.ndarray, mask: jnp.ndarray,
                       history_dtype: str,
                       codebook: Optional[jnp.ndarray] = None
                       ) -> jnp.ndarray:
    """Mean per-row relative L2 error `||v - dq(q(v))|| / ||v||` a push of
    `values` incurs under `history_dtype`, over the `mask`-valid rows
    (`codebook` is required for vq stores). The measurable counterpart
    of the paper's staleness bound: total history error = staleness
    (halo_age_*) + this quantization term.

    This re-quantizes the push payload (the kernel path quantizes inside
    the scatter, so nothing can be shared across the pallas_call
    boundary) — an accepted O(B*d) elementwise cost next to the step's
    O(B*d^2) matmuls, and exactly zero work for f32 stores."""
    codec = get_codec(history_dtype)
    if codec.lossless:
        return jnp.zeros((), jnp.float32)
    v = values.astype(jnp.float32)
    back = codec.roundtrip(v, codebook)
    num = jnp.sqrt(jnp.sum(jnp.square(v - back), axis=-1))
    den = jnp.sqrt(jnp.sum(jnp.square(v), axis=-1)) + 1e-12
    valid = mask.astype(jnp.float32)
    return jnp.sum((num / den) * valid) / jnp.maximum(jnp.sum(valid), 1.0)


def pull(table: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather halo rows. idx is padded with num_nodes-safe dummy (clip)."""
    return jnp.take(table, idx, axis=0, mode="clip")


def push(table: jnp.ndarray, idx: jnp.ndarray, values: jnp.ndarray,
         mask: jnp.ndarray) -> jnp.ndarray:
    """Scatter in-batch rows (padding rows masked out via dummy index)."""
    safe_idx = jnp.where(mask, idx, table.shape[0])  # OOB -> dropped
    return table.at[safe_idx].set(values.astype(table.dtype), mode="drop",
                                  unique_indices=False)


def tick(age: jnp.ndarray, batch_idx: jnp.ndarray,
         mask: jnp.ndarray) -> jnp.ndarray:
    """age += 1 everywhere, reset to 0 for just-pushed nodes."""
    age = age + 1
    safe = jnp.where(mask, batch_idx, age.shape[0])
    return age.at[safe].set(0, mode="drop")


# ---------------------------------------------------------------------------
# Typed runtime store
# ---------------------------------------------------------------------------

@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["tables", "age", "scales", "codebooks",
                                "cb_counts", "cb_sums"],
                   meta_fields=["backend", "history_dtype", "storage"])
@dataclass(frozen=True)
class HistoryStore:
    """Historical-embedding store with the kernel backend bound once.

    A frozen pytree: `tables` (one [N+1, d] array per hidden layer — the
    +1 sentinel row is REQUIRED: padded indices point at it, and the
    push clips valid indices below it, so an [N, d] table would have its
    last real row clobbered), the staleness clock `age`, and (int8
    only) the per-row `scales` tables ([N+1] f32 each) are leaves;
    `backend`, `history_dtype` and `storage` are static aux data, so a
    store created for one backend/precision/placement cannot flow into a
    step traced for another without a re-trace. All methods
    are pure — they return a new store. `pull` always yields dequantized
    rows; `push` takes full-precision rows and quantizes on the way in.

    `storage="host"` pins the tables (and scale vectors) in host RAM via
    the device's host memory kind ("pinned_host" on TPU) — the paper's
    large-graph configuration, where H̄ lives on CPU RAM and only pulled
    rows ever reach the accelerator. `pull` then streams the gathered
    rows device-ward with an async `jax.device_put` (XLA overlaps the
    host->device copy with unrelated compute; see `prefetch`, which the
    epoch pipeline uses to hide the whole pull behind the previous
    batch's backward). A runtime with no host memory kind at all
    (`host_storage_supported`) cannot hold a "host" store: `place`
    raises instead of leaving the tables on the device.
    """
    tables: Tuple[jnp.ndarray, ...]
    age: jnp.ndarray
    scales: Optional[Tuple[jnp.ndarray, ...]] = None
    codebooks: Optional[Tuple[jnp.ndarray, ...]] = None
    cb_counts: Optional[Tuple[jnp.ndarray, ...]] = None
    cb_sums: Optional[Tuple[jnp.ndarray, ...]] = None
    backend: str = "jnp"
    history_dtype: str = "f32"
    storage: str = "device"

    @classmethod
    def create(cls, num_nodes: int, dims: List[int], dtype=None,
               backend: Optional[str] = None,
               history_dtype: Optional[str] = None,
               storage: Optional[str] = None) -> "HistoryStore":
        """`num_nodes` must include the sentinel row (pass N + 1).
        `history_dtype` resolves arg > $REPRO_HISTORY_DTYPE > "f32" and
        `storage` arg > $REPRO_HISTORY_STORAGE > "device";
        `dtype` (legacy) overrides the storage dtype for f32 stores."""
        from repro.kernels import ops
        hd = resolve_history_dtype(history_dtype)
        codec = get_codec(hd)
        st = codec.storage if (hd != "f32" or dtype is None) else dtype
        tables = tuple(jnp.zeros((num_nodes, codec.table_width(d)), st)
                       for d in dims)
        scales = (tuple(jnp.ones((num_nodes,), jnp.float32) for _ in dims)
                  if codec.scaled else None)
        codebooks = (tuple(vq_init_codebook(d) for d in dims)
                     if codec.vq else None)
        counts = (tuple(jnp.zeros(cb.shape[:2], jnp.float32)
                        for cb in codebooks) if codec.vq else None)
        sums = (tuple(jnp.zeros(cb.shape, jnp.float32)
                      for cb in codebooks) if codec.vq else None)
        return cls(tables=tables, age=jnp.zeros((num_nodes,), jnp.int32),
                   scales=scales, codebooks=codebooks, cb_counts=counts,
                   cb_sums=sums, backend=ops.resolve_backend(backend),
                   history_dtype=hd,
                   storage=resolve_history_storage(storage)).place()

    def place(self) -> "HistoryStore":
        """Re-place the tables per `storage` (host memory kind for
        "host" stores) — idempotent, and the re-placement hook after a
        checkpoint restore, whose `jnp.asarray` leaves land in default
        device memory. A "host" store on a runtime without a host memory
        kind raises rather than staying on the device."""
        if self.storage != "host":
            return self
        kind = _memory_kinds()[0]
        if kind is None:
            raise RuntimeError(
                "history storage='host' needs a host memory kind, and "
                f"this runtime ({jax.devices()[0].platform}) has none")
        tables = _put_kind(self.tables, kind)
        scales = (None if self.scales is None
                  else _put_kind(self.scales, kind))
        return replace(self, tables=tables, scales=scales)

    def grow(self, n_new: int) -> "HistoryStore":
        """Extend the store by `n_new` nodes (evolving graphs): fresh
        zero rows are spliced in BEFORE the sentinel row, so existing
        rows, their ages/scales, and the sentinel all keep their
        semantics. A zero row is exactly what `create` initializes for
        every codec — zero f32/bf16 rows, zero int8 codes at scale 1.0,
        zero vq codes (codebook entry 0 is pinned to zero) — so grown
        rows behave as never-pushed. Codebooks and their refit
        statistics are per-layer, not per-node: unchanged."""
        if n_new <= 0:
            return self

        def _splice(a, fill):
            pad = jnp.full((n_new,) + a.shape[1:], fill, a.dtype)
            return jnp.concatenate([a[:-1], pad, a[-1:]], axis=0)

        tables = tuple(_splice(t, 0) for t in self.tables)
        age = _splice(self.age, 0)
        scales = (None if self.scales is None
                  else tuple(_splice(s, 1) for s in self.scales))
        return replace(self, tables=tables, age=age,
                       scales=scales).place()

    @property
    def num_layers(self) -> int:
        return len(self.tables)

    def layer_scales(self, ell: int) -> Optional[jnp.ndarray]:
        """Per-row f32 scale table for layer `ell` (None unless
        int8/vq)."""
        return None if self.scales is None else self.scales[ell]

    def layer_codebook(self, ell: int) -> Optional[jnp.ndarray]:
        """[S, C, ds] f32 codebook for layer `ell` (None unless vq)."""
        return None if self.codebooks is None else self.codebooks[ell]

    def pull(self, ell: int, idx: jnp.ndarray,
             pad_out: bool = False) -> jnp.ndarray:
        """Gather halo rows from H̄^(ell) on the bound backend,
        dequantized (int8/vq rows come back as f32; bf16 rows come back
        as bf16 and upcast where they are consumed). Host stores stream
        the gathered rows device-ward (the [M, d] result, never the
        table). `pad_out=True` keeps the rows zero-padded to the kernel
        lane width (see `ops.pull_rows`) — the halo-split GAT/PNA route
        uses this so no [M, d] float tensor is ever shaped."""
        from repro.kernels import ops
        out = ops.pull_rows(self.tables[ell], idx,
                            scales=self.layer_scales(ell),
                            codebook=self.layer_codebook(ell),
                            backend=self.backend, pad_out=pad_out)
        return self._stream(out)

    def _stream(self, rows: jnp.ndarray) -> jnp.ndarray:
        """Move pulled rows into device memory (async under jit — XLA
        schedules the host->device copy concurrently with compute that
        does not consume it). No-op for device stores / host-less
        runtimes."""
        host_kind, dev_kind = _memory_kinds()
        if self.storage != "host" or host_kind is None or \
                host_kind == dev_kind:
            return rows
        sharding = jax.sharding.SingleDeviceSharding(
            jax.devices()[0], memory_kind=dev_kind)
        return jax.device_put(rows, sharding)

    # -- epoch-level software pipelining support ---------------------------

    def prefetch(self, idx: jnp.ndarray) -> Tuple:
        """Dispatch the halo pull for a FUTURE batch: gather every
        layer's rows for `idx` in raw storage precision (int8 stays
        int8; its per-row scales ride along) and stream them
        device-ward. Returns the per-layer `(rows, scales|None)` tuple
        that `with_pulled` later turns back into a readable store view.

        This is the epoch pipeline's async handle (`runtime.train_epoch`
        with `prefetch_depth > 0`): issued before the CURRENT batch's
        forward/backward, so XLA overlaps the table gather — and, for
        host stores, the host->device row transfer — with that batch's
        compute. No dequant happens here; the rows are the exact table
        bits, which is what keeps the pipelined schedule bit-identical
        (see `patch_pulled` for the write-after-read hazard)."""
        out = []
        for ell in range(self.num_layers):
            rows = jnp.take(self.tables[ell], idx, axis=0, mode="clip")
            scl = (None if self.scales is None else
                   self._stream(jnp.take(self.scales[ell], idx,
                                         mode="clip")))
            out.append((self._stream(rows), scl))
        return tuple(out)

    def with_pulled(self, pulled: Tuple) -> "HistoryStore":
        """A read view whose layer tables ARE the prefetched halo rows
        (`pulled` from `prefetch`): pulling row i of the view returns
        bit-for-bit what pulling halo node i from the full store would —
        same storage bits, same dequant multiplies — so the forward pass
        runs unchanged against [max_h, d] mini-tables instead of the
        [N+1, d] originals. The view keeps the full-size `age` (staleness
        diags read it with the real halo indices) and drops the host
        placement (the mini-tables already live device-side). Push back
        into the ORIGINAL store, never the view."""
        tables = tuple(p[0] for p in pulled)
        scales = (None if self.scales is None
                  else tuple(p[1] for p in pulled))
        return replace(self, tables=tables, scales=scales,
                       storage="device")

    def push(self, ell: int, idx: jnp.ndarray, values: jnp.ndarray,
             mask: jnp.ndarray) -> "HistoryStore":
        """Scatter fresh in-batch rows into H̄^(ell), quantizing to the
        store's history_dtype on the way in. The table's sentinel row is
        declared (`scratch_last_row`): valid rows never land there, and
        the vq kernel path scatters masked rows into it, in place."""
        from repro.kernels import ops
        codec = get_codec(self.history_dtype)
        if codec.vq:
            cb = self.codebooks[ell]
            new, new_s = ops.push_rows_vq(
                self.tables[ell], self.scales[ell], idx, values, mask,
                codebook=cb, backend=self.backend, scratch_last_row=True)
            # k-means E-step for the epoch-cadence refit: re-encode via
            # the shared definition (bitwise what the scatter wrote) and
            # fold the assignments into the running stats.
            codes, ps = vq_encode_rows(values, cb)
            cnt, sm = vq_accumulate_stats(
                codes, values, ps, mask, self.cb_counts[ell],
                self.cb_sums[ell])
            return replace(
                self,
                tables=self.tables[:ell] + (new,) + self.tables[ell + 1:],
                scales=self.scales[:ell] + (new_s,) + self.scales[ell + 1:],
                cb_counts=self.cb_counts[:ell] + (cnt,)
                + self.cb_counts[ell + 1:],
                cb_sums=self.cb_sums[:ell] + (sm,)
                + self.cb_sums[ell + 1:])
        if codec.scaled:
            new, new_s = ops.push_rows_q(
                self.tables[ell], self.scales[ell], idx, values, mask,
                backend=self.backend, scratch_last_row=True)
            scales = self.scales[:ell] + (new_s,) + self.scales[ell + 1:]
            tables = self.tables[:ell] + (new,) + self.tables[ell + 1:]
            return replace(self, tables=tables, scales=scales)
        new = ops.push_rows(self.tables[ell], idx, values, mask,
                            backend=self.backend, scratch_last_row=True)
        tables = self.tables[:ell] + (new,) + self.tables[ell + 1:]
        return replace(self, tables=tables)

    def quant_error(self, values: jnp.ndarray, mask: jnp.ndarray,
                    ell: int = 0) -> jnp.ndarray:
        """Relative error a push of `values` incurs at this precision
        (the `hist_quant_err` diagnostic; exactly 0 for f32 stores).
        `ell` selects the codebook for vq stores."""
        return quantization_error(values, mask, self.history_dtype,
                                  self.layer_codebook(ell))

    def refit_codebooks(self) -> "HistoryStore":
        """Apply the k-means M-step accumulated by this epoch's pushes
        (`vq_refit_codebook`), then re-encode every stored row under the
        new codebook (decoding with the old one first) so codes and
        codebook stay consistent, and reset the stats. No-op for non-vq
        stores. Transiently materializes each layer's f32 table — an
        epoch-cadence host-driven cost (`GASConfig.vq_refit_every`),
        never a per-step one."""
        if not get_codec(self.history_dtype).vq:
            return self
        tables, scales, cbs, cnts, sms = [], [], [], [], []
        for ell in range(self.num_layers):
            cb_old = self.codebooks[ell]
            cb = vq_refit_codebook(cb_old, self.cb_counts[ell],
                                   self.cb_sums[ell])
            rows = vq_decode_rows(self.tables[ell], cb_old,
                                  self.scales[ell])
            q, s = vq_encode_rows(rows, cb)
            tables.append(q)
            scales.append(s)
            cbs.append(cb)
            cnts.append(jnp.zeros_like(self.cb_counts[ell]))
            sms.append(jnp.zeros_like(self.cb_sums[ell]))
        return replace(self, tables=tuple(tables), scales=tuple(scales),
                       codebooks=tuple(cbs), cb_counts=tuple(cnts),
                       cb_sums=tuple(sms)).place()

    def tick(self, batch_idx: jnp.ndarray,
             mask: jnp.ndarray) -> "HistoryStore":
        """Advance the staleness clock (age += 1, just-pushed rows -> 0)."""
        return replace(self, age=tick(self.age, batch_idx, mask))

    def patch_pulled(self, pulled: Tuple, halo_nodes: jnp.ndarray,
                     halo_mask: jnp.ndarray, batch_nodes: jnp.ndarray,
                     batch_mask: jnp.ndarray, pushed: Tuple
                     ) -> Tuple:
        """Resolve the pipeline's write-after-read hazard: `pulled` was
        prefetched for a future batch BEFORE the batch that just ran
        pushed its rows — any of that batch's nodes appearing in the
        future batch's halo are stale in the prefetch. Overwrite exactly
        those rows with the just-pushed payloads (`pushed` — one
        full-precision [max_b, d] array per hidden layer), re-quantized
        through the same `quantize_rows` / storage-dtype cast the push
        itself used, so the patched mini-table is bit-identical to a
        fresh post-push gather and the pipelined epoch replays the
        synchronous schedule exactly.

        O(L * max_h * d) selects per step — noise next to the step's
        O(max_b * d^2) matmuls, and the price of dispatching the pull a
        full step early."""
        n1 = self.age.shape[0]
        max_b = batch_mask.shape[0]
        safe_b = jnp.where(batch_mask, batch_nodes, n1).astype(jnp.int32)
        # pos[n] = row of node n in the just-pushed batch, else -1
        pos = jnp.full((n1,), -1, jnp.int32).at[safe_b].set(
            jnp.arange(max_b, dtype=jnp.int32), mode="drop")
        j = jnp.take(pos, halo_nodes, mode="clip")
        hit = (j >= 0) & halo_mask
        jc = jnp.clip(j, 0, max_b - 1)
        codec = get_codec(self.history_dtype)
        out = []
        for ell, (rows, scl) in enumerate(pulled):
            pay = pushed[ell]
            if codec.scaled:
                q, ps = codec.encode(pay, self.layer_codebook(ell))
                rows = jnp.where(hit[:, None], jnp.take(q, jc, axis=0),
                                 rows)
                scl = jnp.where(hit, jnp.take(ps, jc), scl)
            else:
                cast = pay.astype(rows.dtype)
                rows = jnp.where(hit[:, None],
                                 jnp.take(cast, jc, axis=0), rows)
            out.append((rows, scl))
        return tuple(out)

    def bytes_per_table(self) -> List[int]:
        out = [int(np.prod(t.shape)) * t.dtype.itemsize
               for t in self.tables]
        for aux in (self.scales, self.codebooks, self.cb_counts,
                    self.cb_sums):
            if aux is not None:
                out = [b + int(np.prod(a.shape)) * a.dtype.itemsize
                       for b, a in zip(out, aux)]
        return out

    def bytes(self) -> int:
        return sum(self.bytes_per_table())
