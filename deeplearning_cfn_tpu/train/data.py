"""Input pipelines.

Two capabilities rebuilt from the reference:

- **Synthetic data** for benchmarking, the analog of the Horovod
  ``train_synthetic.sh`` path (README.md:149-163): deterministic on-device
  generation so benchmarks measure compute, not IO.
- **Data-source probing**: pick the fastest storage that actually has the
  dataset, like run.sh:21-35 probing FSx -> EFS -> EBS in speed order.

Real dataset loading (MNIST/CIFAR/ImageNet from disk or GCS) goes through
the same ``Dataset`` protocol so trainers don't care which backs them.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Iterator

import jax
import numpy as np

from deeplearning_cfn_tpu.obs.tracing import span


def probe_data_source(candidates: list[str | Path], marker: str = "") -> Path | None:
    """Return the first candidate directory that exists (and contains
    ``marker`` if given) — speed-ordered probe, run.sh:21-35 style."""
    for cand in candidates:
        p = Path(cand)
        if p.is_dir() and (not marker or (p / marker).exists()):
            return p
    return None


@dataclass
class Batch:
    x: np.ndarray
    y: np.ndarray


@dataclass
class SyntheticDataset:
    """Deterministic synthetic classification data.

    Labels are derived from the inputs so a model can actually fit them —
    loss decreasing on synthetic data is the e2e smoke assertion
    (SURVEY §4's WaitCondition-style check), which pure-noise labels would
    not support.
    """

    shape: tuple[int, ...] = (28, 28, 1)
    num_classes: int = 10
    batch_size: int = 32
    seed: int = 0
    # "uint8" is the compact-transfer dtype: samples are affinely mapped
    # into [0, 255] and quantized, so the host->device payload is 4x
    # smaller than float32 and the dequantize+normalize runs inside the
    # jitted step (``TrainerConfig.input_stats`` = ``self.input_stats``).
    dtype: str = "float32"

    noise_scale: float = 1.0
    # The class templates define the TASK; the seed drives the sample
    # stream.  A held-out split shares template_seed with the training set
    # but uses a different seed — same task, disjoint samples.  None =
    # templates follow ``seed`` (original behavior).
    template_seed: int | None = None
    # Pregenerate a seeded pool of this many batches and cycle through
    # them: the per-step host cost drops to an index, so imagenet-like
    # synthetic benches measure the pipeline, not standard_normal.  None
    # keeps fresh per-step sampling (the convergence-test path — cycling
    # repeats samples, fine for throughput, wrong for loss curves).
    pool_batches: int | None = None

    # Samples land roughly in templates±(3-4)sigma; the affine map
    # (x * SCALE + OFFSET) * 255 puts that range inside [0, 255] with
    # slight clipping at the tails.  input_stats inverts it exactly.
    _U8_OFFSET = 0.5
    _U8_SCALE = 0.125

    @property
    def input_stats(self) -> tuple[tuple[float, ...], tuple[float, ...]] | None:
        """Per-channel (mean, std) in the /255 domain that make the
        in-step ``dequantize_normalize`` invert the uint8 quantization —
        pass straight to ``TrainerConfig.input_stats``.  None for float
        dtypes (no normalization needed)."""
        if self.dtype != "uint8":
            return None
        c = int(self.shape[-1])
        return ((self._U8_OFFSET,) * c, (self._U8_SCALE,) * c)

    def _quantize(self, x: np.ndarray) -> np.ndarray:
        scaled = (x * self._U8_SCALE + self._U8_OFFSET) * 255.0
        return np.clip(np.rint(scaled), 0, 255).astype(np.uint8)

    def _finalize(self, x: np.ndarray) -> np.ndarray:
        return self._quantize(x) if self.dtype == "uint8" else x.astype(self.dtype)

    def _templates(self, rng: np.random.Generator) -> np.ndarray:
        template_rng = (
            np.random.default_rng(self.template_seed)
            if self.template_seed is not None
            else rng
        )
        return template_rng.standard_normal(
            (self.num_classes, *self.shape)
        ).astype(np.float32)

    def batches(self, steps: int) -> Iterator[Batch]:
        if self.pool_batches:
            yield from self._pooled_batches(steps)
            return
        rng = np.random.default_rng(self.seed)
        # Each class has a fixed random template; samples are template +
        # noise.  Learnable in a few dozen steps, so "loss decreases" is a
        # meaningful assertion, while noise keeps it from being trivial.
        templates = self._templates(rng)
        for _ in range(steps):
            y = rng.integers(0, self.num_classes, size=self.batch_size).astype(np.int32)
            noise = rng.standard_normal((self.batch_size, *self.shape)).astype(
                np.float32
            )
            x = self._finalize(templates[y] + self.noise_scale * noise)
            yield Batch(x=x, y=y)

    def _pooled_batches(self, steps: int) -> Iterator[Batch]:
        """Vectorized pool generation: ONE rng call for all K batches'
        labels and one for the noise, then cycle — per-step host cost is
        an index into preallocated arrays."""
        rng = np.random.default_rng(self.seed)
        templates = self._templates(rng)
        # The pool is always the FULL pool_batches, never clamped to
        # ``steps``: clamping would make the stream's contents depend on
        # how many steps the caller asked for, breaking same-seed
        # reproducibility between short and long runs.
        k = max(1, int(self.pool_batches))
        y = rng.integers(
            0, self.num_classes, size=(k, self.batch_size)
        ).astype(np.int32)
        noise = rng.standard_normal(
            (k, self.batch_size, *self.shape), dtype=np.float32
        )
        x = self._finalize(templates[y] + self.noise_scale * noise)
        for i in range(steps):
            b = i % k
            yield Batch(x=x[b], y=y[b])

    @classmethod
    def mnist_like(cls, batch_size: int, seed: int = 0) -> "SyntheticDataset":
        return cls(shape=(28, 28, 1), num_classes=10, batch_size=batch_size, seed=seed)

    @classmethod
    def imagenet_like(
        cls,
        batch_size: int,
        image_size: int = 224,
        seed: int = 0,
        dtype: str = "float32",
        pool_batches: int | None = None,
    ) -> "SyntheticDataset":
        return cls(
            shape=(image_size, image_size, 3),
            num_classes=1000,
            batch_size=batch_size,
            seed=seed,
            dtype=dtype,
            pool_batches=pool_batches,
        )


@dataclass
class SyntheticTokenDataset:
    """Synthetic LM token streams for BERT/Llama-style trainers."""

    seq_len: int = 512
    vocab_size: int = 32000
    batch_size: int = 8
    seed: int = 0

    def batches(self, steps: int) -> Iterator[Batch]:
        rng = np.random.default_rng(self.seed)
        for _ in range(steps):
            tokens = rng.integers(
                1, self.vocab_size, size=(self.batch_size, self.seq_len), dtype=np.int32
            )
            # Next-token targets: inputs shifted left (causal LM objective).
            yield Batch(x=tokens, y=np.roll(tokens, -1, axis=1))


@dataclass
class SyntheticMLMDataset:
    """Masked-LM batches: 15% of tokens masked; targets are the original
    ids at masked positions and -1 (ignore) elsewhere.  Token streams have
    learnable structure (each position's distribution depends on the
    previous token) so MLM loss genuinely decreases."""

    seq_len: int = 128
    vocab_size: int = 1000
    batch_size: int = 8
    seed: int = 0
    mask_token: int = 0
    mask_prob: float = 0.15
    # The TASK (the Markov transition permutation) is seeded separately
    # from the samples — the SyntheticSeqClassificationDataset
    # template_seed convention — so a held-out eval set (different
    # ``seed``) measures generalization on the SAME transition function
    # instead of scoring the model against a different task.
    structure_seed: int = 0

    def batches(self, steps: int) -> Iterator[Batch]:
        rng = np.random.default_rng(self.seed)
        # Markov structure: token[i+1] = f(token[i]) + small noise.
        perm = np.random.default_rng(self.structure_seed).permutation(
            self.vocab_size
        )
        for _ in range(steps):
            tokens = np.empty((self.batch_size, self.seq_len), np.int32)
            tokens[:, 0] = rng.integers(1, self.vocab_size, self.batch_size)
            for i in range(1, self.seq_len):
                tokens[:, i] = perm[tokens[:, i - 1]]
            masked = rng.random((self.batch_size, self.seq_len)) < self.mask_prob
            x = np.where(masked, self.mask_token, tokens).astype(np.int32)
            y = np.where(masked, tokens, -1).astype(np.int32)
            yield Batch(x=x, y=y)


@dataclass
class SyntheticSeqClassificationDataset:
    """Labeled token sequences for classifier fine-tuning smokes: each
    class has its own categorical distribution over the vocabulary
    (template logits), so labels are learnable from token statistics but
    not trivially from any single position.  ``template_seed`` follows the
    SyntheticDataset convention (same task, disjoint sample streams)."""

    batch_size: int = 32
    seq_len: int = 32
    vocab_size: int = 64
    num_classes: int = 4
    seed: int = 0
    template_seed: int | None = None

    def batches(self, steps: int) -> Iterator[Batch]:
        rng = np.random.default_rng(self.seed)
        template_rng = (
            np.random.default_rng(self.template_seed)
            if self.template_seed is not None
            else rng
        )
        logits = 2.0 * template_rng.standard_normal(
            (self.num_classes, self.vocab_size)
        )
        probs = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
        for _ in range(steps):
            y = rng.integers(0, self.num_classes, size=self.batch_size).astype(
                np.int32
            )
            x = np.stack(
                [
                    rng.choice(self.vocab_size, size=self.seq_len, p=probs[label])
                    for label in y
                ]
            ).astype(np.int32)
            yield Batch(x=x, y=y)


@dataclass
class SyntheticDetectionDataset:
    """Synthetic detection batches: images containing colored rectangles,
    one color template per class, with padded ground truth —
    ``y = {"boxes": [B, M, 4] (y1,x1,y2,x2 pixels), "classes": [B, M]}``
    padded with zeros / -1.  Box fill color encodes the class, so both the
    classification and box-regression heads have learnable signal (the
    loss-decreases smoke assertion, SURVEY §4)."""

    image_size: int = 128
    num_classes: int = 8
    max_boxes: int = 5
    batch_size: int = 8
    seed: int = 0
    # Class->color templates define the TASK (same convention as
    # SyntheticDataset.template_seed): held-out splits share template_seed
    # with training but use a different seed.
    template_seed: int | None = None
    # Instance masks at stride ``mask_stride`` (y["masks"]: [B, M, h, w]
    # uint8, exact rectangle fills) — the training signal for the
    # prototype-mask head (run.sh:86 MODE_MASK=True analog).
    with_masks: bool = False
    mask_stride: int = 8

    def batches(self, steps: int) -> Iterator[Batch]:
        rng = np.random.default_rng(self.seed)
        template_rng = (
            np.random.default_rng(self.template_seed)
            if self.template_seed is not None
            else rng
        )
        colors = template_rng.uniform(
            0.5, 1.5, size=(self.num_classes, 3)
        ).astype(np.float32)
        s = self.image_size
        ms = s // self.mask_stride
        for _ in range(steps):
            x = rng.normal(0.0, 0.05, size=(self.batch_size, s, s, 3)).astype(
                np.float32
            )
            boxes = np.zeros((self.batch_size, self.max_boxes, 4), np.float32)
            classes = np.full((self.batch_size, self.max_boxes), -1, np.int32)
            masks = (
                np.zeros((self.batch_size, self.max_boxes, ms, ms), np.uint8)
                if self.with_masks
                else None
            )
            for b in range(self.batch_size):
                n = int(rng.integers(1, self.max_boxes + 1))
                for i in range(n):
                    h = int(rng.integers(s // 8, s // 2))
                    w = int(rng.integers(s // 8, s // 2))
                    y0 = int(rng.integers(0, s - h))
                    x0 = int(rng.integers(0, s - w))
                    c = int(rng.integers(0, self.num_classes))
                    x[b, y0 : y0 + h, x0 : x0 + w] += colors[c]
                    boxes[b, i] = (y0, x0, y0 + h, x0 + w)
                    classes[b, i] = c
                    if masks is not None:
                        st = self.mask_stride
                        masks[b, i,
                              y0 // st : max(y0 // st + 1, (y0 + h) // st),
                              x0 // st : max(x0 // st + 1, (x0 + w) // st)] = 1
            y = {"boxes": boxes, "classes": classes}
            if masks is not None:
                y["masks"] = masks
            yield Batch(x=x, y=y)


def device_put_batch(batch: Batch, sharding) -> tuple[jax.Array, jax.Array]:
    """Place a host batch onto the mesh with the batch sharding — the only
    host->device transfer in the hot loop.  Leaves already carrying an
    equivalent sharding (prefetched batches) pass through untouched."""
    return (
        device_put_tree(batch.x, sharding),
        device_put_tree(batch.y, sharding),
    )


def _placed_with(leaf, sharding) -> bool:
    """True when ``leaf`` is a LIVE committed jax.Array already laid out
    as ``sharding`` — re-issuing device_put for it would at best be a
    no-op and at worst a layout check on the hot path.

    Liveness matters: a donated/deleted array keeps its sharding
    metadata, so without the ``is_deleted`` check the skip would hand a
    dead buffer back to the caller and the failure ("Array has been
    deleted") would surface at first use, far from the placement site.
    Treating deleted as not-placed makes ``jax.device_put`` raise right
    here instead."""
    if not isinstance(leaf, jax.Array):
        return False
    try:
        if leaf.is_deleted():
            return False
    except AttributeError:
        pass
    current = getattr(leaf, "sharding", None)
    if current is None:
        return False
    if current == sharding:
        return True
    try:
        return current.is_equivalent_to(sharding, leaf.ndim)
    except (AttributeError, TypeError, ValueError):
        return False


def device_put_tree(tree, sharding):
    """``jax.device_put`` each leaf of a batch pytree UNLESS it already
    carries an equivalent sharding (the prefetcher placed it): the
    trainer's per-step transfer becomes an identity check for prefetched
    batches instead of relying on device_put's no-op path."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf
        if _placed_with(leaf, sharding)
        else jax.device_put(leaf, sharding),
        tree,
    )


def stack_batches(batches: Iterator[Batch], k: int) -> Iterator[Batch]:
    """Fold ``k`` consecutive host batches into one leading-axis stack:
    ``Batch(x=[k, B, ...], y=[k, B, ...])`` — the pre-staged input shape
    ``Trainer.multi_step_fn(k)`` scans over.  Stacking happens host-side
    (numpy), BEFORE the DevicePrefetcher's ``device_put``, so a whole
    k-step stack crosses PCIe as one transfer and lands device-resident
    ahead of the dispatch that consumes it.  A trailing ragged group
    (fewer than ``k`` batches left) is NOT yielded — callers route the
    remainder through the single-step path."""
    if k < 1:
        raise ValueError(f"stack_batches needs k >= 1, got {k}")
    group: list[Batch] = []
    for b in batches:
        group.append(b)
        if len(group) == k:
            yield Batch(
                x=jax.tree_util.tree_map(lambda *ls: np.stack(ls), *[g.x for g in group]),
                y=jax.tree_util.tree_map(lambda *ls: np.stack(ls), *[g.y for g in group]),
            )
            group = []


def donate_buffers(tree) -> int:
    """Explicitly free the device buffers of a consumed batch tree and
    return the bytes released.

    XLA donation is strictly input->output aliasing, and a training
    batch has no same-shaped output to alias into — ``donate_argnums``
    on the batch operands would only emit "donated buffers were not
    usable" warnings and free nothing.  So batch "donation" is this:
    the loop deletes the buffers it placed itself as soon as the step
    consuming them has been dispatched.  Deletion is safe in-flight
    (the runtime holds execution references until the step completes);
    what it guarantees is that the NEXT prefetched batch never waits on
    HBM still pinned by an already-consumed one.  Only call this on
    buffers the caller placed — never on arrays handed in from outside
    the loop."""
    freed = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array) and not leaf.is_deleted():
            freed += leaf.nbytes
            leaf.delete()
    return freed


class DevicePrefetcher:
    """Background host→device pipeline: producer threads pull batches
    from the host iterator (loader decode, batching) and issue the
    ``device_put`` up to ``size`` batches ahead, so input transfer
    overlaps the previous step's compute instead of sitting on the
    critical path.  The TPU equivalent of the double-buffered input
    pipelines the reference's external frameworks provided (SURVEY §2.2).

    ``workers`` > 1 runs a small pool: the source iterator is pulled
    under a lock (host decode stays ordered and exceptions deterministic)
    while the transfers themselves proceed in parallel, feeding a
    sequence-numbered reorder buffer — iteration order is EXACTLY the
    source order and a source exception re-raises at the position it
    occurred, identical to the single-worker path.

    ``stats`` (a :class:`~deeplearning_cfn_tpu.train.pipeline.PipelineStats`)
    counts transfer bytes, host-input seconds, producer stalls and
    consumer waits; ``close()`` journals it once via the obs plane.

    ``close()`` (or exhausting the iterator) stops the producers —
    abandoned early-exit consumers do not leak a blocked thread.
    """

    _DONE = object()

    def __init__(
        self,
        batches: Iterator[Batch],
        sharding,
        size: int = 2,
        workers: int = 1,
        stats=None,
        profiler=None,
    ):
        import threading

        self._src = iter(batches)
        self._sharding = sharding
        self._size = max(1, size)
        self._stats = stats
        # Optional obs.profiler.StepProfiler: producer-side device_put
        # time folds into its "h2d" phase with critical=False — the
        # transfer overlaps compute, so it informs the phase stats but
        # is not subtracted from the consumer's host residual.
        self._profiler = profiler
        self._stop = threading.Event()
        # _src_lock serializes source pulls (sequence assignment); _cond
        # guards the reorder buffer and the consumer cursor.
        self._src_lock = threading.Lock()
        self._cond = threading.Condition()
        self._buf: dict[int, object] = {}  # seq -> Batch | exception | _DONE
        self._next_pull = 0  # next sequence number (under _src_lock)
        self._next_out = 0  # next sequence the consumer emits (under _cond)
        self._done = False  # source exhausted/raised (under _src_lock)
        self._threads = [
            threading.Thread(target=self._produce, daemon=True)
            for _ in range(max(1, int(workers)))
        ]
        for t in self._threads:
            t.start()

    def _pull(self):
        """One serialized source pull -> (seq, item); item is a Batch, an
        exception (re-raised consumer-side at this position), _DONE, or
        None when another worker already hit the end."""
        with self._src_lock:
            if self._done or self._stop.is_set():
                return None, None
            seq = self._next_pull
            t0 = time.perf_counter()
            try:
                item = next(self._src)
            except StopIteration:
                item = self._DONE
            except BaseException as e:  # dlcfn: noqa[DLC004] not swallowed: re-raised in the consumer's __iter__
                item = e
            if self._stats is not None:
                self._stats.add_host_input(time.perf_counter() - t0)
            self._next_pull = seq + 1
            if item is self._DONE or isinstance(item, BaseException):
                self._done = True
            return seq, item

    def _produce(self) -> None:
        while not self._stop.is_set():
            seq, item = self._pull()
            if seq is None:
                return
            terminal = item is self._DONE or isinstance(item, BaseException)
            if not terminal:
                if self._stats is not None:
                    from deeplearning_cfn_tpu.train.pipeline import nbytes_of

                    self._stats.add_transfer(nbytes_of((item.x, item.y)))
                t_put = time.perf_counter()
                try:
                    # A seam of ours on the producer's own line of a profile:
                    # the runtime's re-tiling of the batch for the transfer
                    # (Transpose, XlaLinearize) happens under it.
                    with span("prefetch.h2d", journal=False):
                        item = Batch(*device_put_batch(item, self._sharding))
                except BaseException as e:  # dlcfn: noqa[DLC004] not swallowed: re-raised in the consumer's __iter__
                    # A transfer that fails (a batch the mesh cannot
                    # divide, device memory exhausted) ends the stream at
                    # this position; dying silently here would leave the
                    # consumer waiting for a batch that never comes.
                    item, terminal = e, True
                    with self._src_lock:
                        self._done = True
                if self._profiler is not None:
                    self._profiler.fold(
                        "h2d", time.perf_counter() - t_put, critical=False
                    )
            t0 = time.perf_counter()
            with self._cond:
                # Bound the buffer to ``size`` batches ahead of the
                # consumer (terminal markers always land — they are the
                # stream's end, not payload).
                while (
                    not terminal
                    and seq >= self._next_out + self._size
                    and not self._stop.is_set()
                ):
                    self._cond.wait(0.1)
                if self._stop.is_set():
                    return
                self._buf[seq] = item
                self._cond.notify_all()
            if self._stats is not None and not terminal:
                self._stats.add_producer_stall(time.perf_counter() - t0)
            if terminal:
                return

    def __iter__(self) -> Iterator[Batch]:
        # try/finally so an abandoned generator (consumer breaks out of its
        # for-loop without close()) still stops the producer on GC.
        try:
            while True:
                t0 = time.perf_counter()
                with self._cond:
                    while (
                        self._next_out not in self._buf
                        and not self._stop.is_set()
                    ):
                        self._cond.wait(0.1)
                    if self._next_out not in self._buf:
                        return  # stopped
                    item = self._buf.pop(self._next_out)
                    self._next_out += 1
                    self._cond.notify_all()
                if self._stats is not None:
                    self._stats.add_consumer_wait(time.perf_counter() - t0)
                if item is self._DONE:
                    return
                if isinstance(item, BaseException):
                    raise item
                yield item
        finally:
            self.close()

    def buffered(self) -> list[Batch]:
        """Snapshot of the batches currently staged ahead of the
        consumer — each already device-resident (the producer issued its
        ``device_put`` before inserting).  Introspection for structural
        overlap checks (scripts/perf_smoke.py asserts the double buffer
        actually holds >= 2 device batches); not part of the hot loop."""
        with self._cond:
            return [b for b in self._buf.values() if isinstance(b, Batch)]

    def close(self) -> None:
        self._stop.set()
        with self._cond:
            self._cond.notify_all()
        if self._stats is not None:
            self._stats.journal()

    def __enter__(self) -> "DevicePrefetcher":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


def mnist_dir_candidates() -> list[str]:
    """Default MNIST search path: shared-storage mount first, then local."""
    return [
        os.environ.get("DEEPLEARNING_STORAGE_MOUNT", "/mnt/dlcfn") + "/data/mnist",
        os.path.expanduser("~/.cache/dlcfn/mnist"),
    ]
