"""ctypes binding for the native data loader (native/dataloader).

The hot input path: C++ reader threads pread fixed-size records straight
into pooled batch buffers (record-level shuffle, per-worker sharding,
bounded prefetch queue) while Python only hands finished buffers to
``jax.device_put``.  This is the framework's native replacement for the
loader work the reference outsourced to its external frameworks (SURVEY
§2.2) — the accelerator never waits on per-example Python.

Builds the shared library via make on first use (g++, same pattern as the
rendezvous broker).  ``NativeRecordLoader.batches()`` yields
:class:`~deeplearning_cfn_tpu.train.data.Batch`, so it drops into
``Trainer.fit`` anywhere a synthetic dataset does.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from dataclasses import dataclass, field
from pathlib import Path
from typing import Iterator, Sequence

import numpy as np

from deeplearning_cfn_tpu.train.data import Batch
from deeplearning_cfn_tpu.train.records import HEADER, RecordSpec, read_header
from deeplearning_cfn_tpu.utils.logging import get_logger

log = get_logger("dlcfn.loader")

LOADER_DIR = Path(__file__).resolve().parents[2] / "native" / "dataloader"
LOADER_SO = LOADER_DIR / "libdlcfn_loader.so"

_lib = None


class LoaderError(RuntimeError):
    pass


class ShardFileError(LoaderError):
    """A shard file is missing or truncated — typed so callers can tell
    a staging problem (re-stage the shard) from a loader problem (build
    failure, bad arguments) without parsing errno prose.  ``reason`` is
    ``"missing"`` or ``"truncated"``; ``path`` is the offending file."""

    def __init__(self, path: str | Path, reason: str, detail: str = ""):
        self.path = Path(path)
        self.reason = reason
        msg = f"{path}: {reason} shard file"
        if detail:
            msg = f"{msg} ({detail})"
        super().__init__(msg)


def validate_shards(paths: Sequence[str | Path], spec: RecordSpec) -> None:
    """Typed validation every loader backend shares: existence, header,
    payload length vs the header's record count, spec record size."""
    if not paths:
        raise LoaderError("no record files given")
    for p in paths:
        path = Path(p)
        if not path.exists():
            raise ShardFileError(path, "missing")
        record_size, n_records = read_header(path)
        if record_size != spec.record_size:
            raise LoaderError(
                f"{path}: record_size {record_size} != spec {spec.record_size}"
            )
        want = HEADER.size + n_records * record_size
        have = os.path.getsize(path)
        if have < want:
            raise ShardFileError(
                path,
                "truncated",
                f"header promises {n_records} records "
                f"({want} bytes), file has {have}",
            )


def _build_library() -> None:
    """``make`` the loader: a no-op when the library is newer than its
    source, a rebuild when it is not — so a stale or foreign artefact
    left in the tree is never what gets loaded."""
    # Bounded: a wedged compiler must fail the build, not hang training.
    proc = subprocess.run(
        ["make", "-C", str(LOADER_DIR)], capture_output=True, text=True,
        timeout=600,
    )
    if proc.returncode != 0:
        raise LoaderError(f"building native loader failed:\n{proc.stderr}")


def _load_library() -> ctypes.CDLL:
    global _lib
    if _lib is not None:
        return _lib
    _build_library()
    lib = ctypes.CDLL(str(LOADER_SO))
    lib.dlcfn_loader_open.restype = ctypes.c_void_p
    lib.dlcfn_loader_open.argtypes = [
        ctypes.POINTER(ctypes.c_char_p),
        ctypes.c_int,  # n_paths
        ctypes.c_int,  # batch_size
        ctypes.c_int,  # n_threads
        ctypes.c_int,  # shard_index
        ctypes.c_int,  # shard_count
        ctypes.c_int,  # shuffle
        ctypes.c_int,  # drop_remainder
        ctypes.c_int,  # loop
        ctypes.c_uint64,  # seed
        ctypes.c_uint64,  # start_batch
        ctypes.c_char_p,  # err_out
        ctypes.c_int,  # err_cap
    ]
    lib.dlcfn_loader_record_size.restype = ctypes.c_uint32
    lib.dlcfn_loader_record_size.argtypes = [ctypes.c_void_p]
    lib.dlcfn_loader_shard_records.restype = ctypes.c_uint64
    lib.dlcfn_loader_shard_records.argtypes = [ctypes.c_void_p]
    lib.dlcfn_loader_batches_per_epoch.restype = ctypes.c_uint64
    lib.dlcfn_loader_batches_per_epoch.argtypes = [ctypes.c_void_p]
    lib.dlcfn_loader_next.restype = ctypes.c_int
    lib.dlcfn_loader_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_uint8)]
    lib.dlcfn_loader_error.restype = ctypes.c_char_p
    lib.dlcfn_loader_error.argtypes = [ctypes.c_void_p]
    lib.dlcfn_loader_close.restype = None
    lib.dlcfn_loader_close.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


@dataclass
class NativeRecordLoader:
    """Threaded shuffling reader over DLC1 files.

    shard_index/shard_count partition records round-robin across SPMD
    workers (each process reads only its shard, like the per-worker data
    split the reference got from per-rank dataset sharding).
    """

    paths: Sequence[str | Path]
    spec: RecordSpec
    batch_size: int
    n_threads: int = 4
    shard_index: int = 0
    shard_count: int = 1
    shuffle: bool = True
    drop_remainder: bool = True
    loop: bool = True
    seed: int = 0
    # Resume position: the global batch index (across epochs) to start
    # at — one batch per training step, so a run restored at step N
    # passes start_batch=N and the stream continues where the lost run
    # stopped instead of replaying the head of the shuffle order (which
    # over-weights early records and may never reach the tail).  Every
    # epoch's permutation is a pure function of (seed, epoch), so the
    # position is exactly reproducible in a fresh process.
    start_batch: int = 0
    _handle: int | None = field(default=None, repr=False)

    def __post_init__(self) -> None:
        validate_shards(self.paths, self.spec)
        lib = _load_library()
        c_paths = (ctypes.c_char_p * len(self.paths))(
            *[str(p).encode() for p in self.paths]
        )
        err = ctypes.create_string_buffer(512)
        handle = lib.dlcfn_loader_open(
            c_paths,
            len(self.paths),
            self.batch_size,
            self.n_threads,
            self.shard_index,
            self.shard_count,
            int(self.shuffle),
            int(self.drop_remainder),
            int(self.loop),
            self.seed,
            self.start_batch,
            err,
            len(err),
        )
        if not handle:
            raise LoaderError(err.value.decode() or "loader open failed")
        self._handle = handle
        self._buf = np.empty(
            (self.batch_size, self.spec.record_size), dtype=np.uint8
        )

    def _live_handle(self) -> int:
        if self._handle is None:
            raise LoaderError("loader is closed")
        return self._handle

    # --- introspection ----------------------------------------------------
    @property
    def shard_records(self) -> int:
        return int(_load_library().dlcfn_loader_shard_records(self._live_handle()))

    @property
    def batches_per_epoch(self) -> int:
        return int(
            _load_library().dlcfn_loader_batches_per_epoch(self._live_handle())
        )

    # --- iteration --------------------------------------------------------
    def next_raw(self, copy: bool = True) -> np.ndarray | None:
        """[n, record_size] u8 for the next batch, or None at end of data.

        With ``copy=False`` the returned array is a view into the loader's
        single reuse buffer — valid only until the next ``next_raw`` call
        (the next batch is memcpy'd over it).  Only use it when the bytes
        are consumed (decoded / device_put) before the next call.
        """
        handle = self._live_handle()
        lib = _load_library()
        n = lib.dlcfn_loader_next(
            handle,
            self._buf.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        )
        if n < 0:
            raise LoaderError(lib.dlcfn_loader_error(handle).decode())
        if n == 0:
            return None
        out = self._buf[:n]
        return out.copy() if copy else out

    def batches(self, steps: int | None = None) -> Iterator[Batch]:
        """Yield decoded Batch objects (x, y fields of the spec)."""
        i = 0
        while steps is None or i < steps:
            # copy=False: decode_batch copies field slices out of the reuse
            # buffer before the next call can overwrite it.
            raw = self.next_raw(copy=False)
            if raw is None:
                return
            arrays = self.spec.decode_batch(raw)
            yield Batch(x=arrays["x"], y=arrays["y"])
            i += 1

    def close(self) -> None:
        if self._handle is not None:
            _load_library().dlcfn_loader_close(self._handle)
            self._handle = None

    def __enter__(self) -> "NativeRecordLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


@dataclass
class PythonRecordLoader:
    """Pure-Python fallback with the native loader's interface and
    guarantees: round-robin sharding over the global record index
    (``g = shard_index; g += shard_count``), a fresh per-epoch
    permutation that is a pure function of (seed, epoch), exactly-once
    per epoch, and ``start_batch`` resume.  NOT byte-identical to the
    native order (numpy's Generator vs std::shuffle over mt19937_64) —
    a run must finish on the backend it started on, which is why
    :func:`open_record_loader` journals the fallback instead of
    silently degrading.
    """

    paths: Sequence[str | Path]
    spec: RecordSpec
    batch_size: int
    n_threads: int = 4  # accepted for interface parity; single-threaded
    shard_index: int = 0
    shard_count: int = 1
    shuffle: bool = True
    drop_remainder: bool = True
    loop: bool = True
    seed: int = 0
    start_batch: int = 0
    _rows: list[np.ndarray] = field(default_factory=list, repr=False)

    def __post_init__(self) -> None:
        validate_shards(self.paths, self.spec)
        if not (0 <= self.shard_index < self.shard_count):
            raise LoaderError(
                f"shard_index {self.shard_index} not in [0, {self.shard_count})"
            )
        counts, starts, at = [], [], 0
        for p in self.paths:
            record_size, n = read_header(p)
            counts.append(n)
            starts.append(at)
            at += n
            self._rows.append(
                np.memmap(
                    p, dtype=np.uint8, mode="r", offset=HEADER.size,
                    shape=(n * record_size,),
                ).reshape(n, record_size)
            )
        self._starts = np.asarray(starts, dtype=np.int64)
        total = at
        self._shard_globals = np.arange(
            self.shard_index, total, self.shard_count, dtype=np.int64
        )
        n_batches = (
            len(self._shard_globals) // self.batch_size
            if self.drop_remainder
            else -(-len(self._shard_globals) // self.batch_size)
        )
        if n_batches == 0:
            raise LoaderError(
                f"shard has {len(self._shard_globals)} records, fewer than "
                f"one batch of {self.batch_size} (drop_remainder={self.drop_remainder})"
            )
        self._bpe = n_batches
        self._epoch = self.start_batch // n_batches
        self._next_in_epoch = self.start_batch % n_batches
        self._order = self._epoch_order(self._epoch)
        self._closed = False

    def _epoch_order(self, epoch: int) -> np.ndarray:
        if not self.shuffle:
            return self._shard_globals
        rng = np.random.default_rng(
            np.random.SeedSequence([int(self.seed), int(epoch)])
        )
        return self._shard_globals[rng.permutation(len(self._shard_globals))]

    # --- introspection (interface parity with NativeRecordLoader) --------
    @property
    def shard_records(self) -> int:
        return int(len(self._shard_globals))

    @property
    def batches_per_epoch(self) -> int:
        return int(self._bpe)

    # --- iteration --------------------------------------------------------
    def next_raw(self, copy: bool = True) -> np.ndarray | None:
        if self._closed:
            raise LoaderError("loader is closed")
        if self._next_in_epoch >= self._bpe:
            if not self.loop:
                return None
            self._epoch += 1
            self._next_in_epoch = 0
            self._order = self._epoch_order(self._epoch)
        lo = self._next_in_epoch * self.batch_size
        ids = self._order[lo : lo + self.batch_size]
        self._next_in_epoch += 1
        files = np.searchsorted(self._starts, ids, side="right") - 1
        out = np.empty((len(ids), self.spec.record_size), dtype=np.uint8)
        for i, (f, g) in enumerate(zip(files, ids)):
            out[i] = self._rows[f][g - self._starts[f]]
        return out

    def batches(self, steps: int | None = None) -> Iterator[Batch]:
        i = 0
        while steps is None or i < steps:
            raw = self.next_raw()
            if raw is None:
                return
            arrays = self.spec.decode_batch(raw)
            yield Batch(x=arrays["x"], y=arrays["y"])
            i += 1

    def close(self) -> None:
        self._closed = True
        self._rows = []

    def __enter__(self) -> "PythonRecordLoader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def open_record_loader(
    paths: Sequence[str | Path],
    spec: RecordSpec,
    batch_size: int,
    *,
    force_python: bool = False,
    **kwargs,
) -> NativeRecordLoader | PythonRecordLoader:
    """The loader entry point callers should use: native when the
    shared library builds, pure-Python otherwise — journaled as a
    ``datastream`` event (``event: "native_fallback"``) so a degraded
    input path is visible in ``dlcfn status --journal``, never silent.

    Shard validation (typed :class:`ShardFileError`) runs FIRST: a
    missing or truncated shard raises on every backend — the fallback
    is for loader failures, not data failures.
    """
    validate_shards(paths, spec)
    if not force_python:
        try:
            return NativeRecordLoader(
                paths=paths, spec=spec, batch_size=batch_size, **kwargs
            )
        except ShardFileError:
            raise
        except LoaderError as exc:
            _record_fallback(str(exc))
            log.warning(
                "native loader unavailable (%s); falling back to the "
                "pure-Python reader", exc,
            )
    return PythonRecordLoader(
        paths=paths, spec=spec, batch_size=batch_size, **kwargs
    )


def _record_fallback(error: str) -> None:
    try:
        from deeplearning_cfn_tpu.obs.recorder import get_recorder

        get_recorder().record(
            "datastream", event="native_fallback", error=error[:500]
        )
    except Exception:  # pragma: no cover - journaling is best-effort
        pass
