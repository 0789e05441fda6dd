"""The SPMD trainer — the compute-path heart of the framework.

Replaces all three of the reference's data-parallel strategies
(SURVEY §2.3) with one compiled SPMD program over a named mesh:

- Horovod ring-allreduce DP (run.sh:70-95): here, batch sharded over the
  ``dp``/``fsdp`` mesh axes with replicated (dp) params — XLA emits the
  gradient all-reduce over ICI inside the compiled step; no background
  daemon, no fusion-threshold tuning (HOROVOD_FUSION_THRESHOLD,
  NCCL_MIN_NRINGS — run.sh:70-79 — have no equivalent because XLA fuses
  and schedules collectives at compile time).
- MXNet dist_device_sync kvstore (README.md:139): same program — device-side
  gradient aggregation IS the psum.
- TF async parameter servers (cifar10_multi_machine_train.py:65-113): not
  reproduced as-is (async PS is an anti-pattern on TPU); its capability —
  scaling input + update throughput across workers — is covered by the same
  synchronous SPMD step, which is also what replaced PS training in practice.

Beyond the reference, the trainer adds FSDP (ZeRO-3-style parameter +
optimizer sharding via the ``fsdp`` axis), bf16 compute, and gradient
rematerialization — the BASELINE.json Llama-3 8B config requires them.

Everything is a single jitted function: params/opt-state shardings declared
via NamedSharding, inputs arriving batch-sharded, outputs donated.  No
Python in the hot loop beyond feeding batches.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import time
from dataclasses import dataclass, field
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning_cfn_tpu.parallel.overlap import (
    ErrorFeedbackState,
    build_overlap_grad_fn,
    error_feedback_shardings,
    init_error_feedback,
    plan_buckets,
)
from deeplearning_cfn_tpu.parallel.sharding import (
    bytes_by_device,
    infer_param_sharding,
    replicated,
)
from deeplearning_cfn_tpu.train.data import device_put_batch, device_put_tree
from deeplearning_cfn_tpu.train.metrics import (
    ThroughputLogger,
    peak_flops_per_chip,
)
from deeplearning_cfn_tpu.obs.recorder import get_recorder
from deeplearning_cfn_tpu.obs.tracing import (
    Drains,
    counter,
    freeze_counters,
    span,
    spans_between,
)
from deeplearning_cfn_tpu.utils.logging import get_logger

log = get_logger("dlcfn.trainer")


class TrainState(struct.PyTreeNode):
    step: jax.Array
    params: Any
    opt_state: Any
    # Mutable model collections (e.g. BatchNorm running stats).  Under GSPMD
    # the batch axis is sharded but program semantics are global, so batch
    # statistics are computed over the GLOBAL batch automatically — the
    # capability the reference needed SyncBN for (run.sh:60-61) falls out of
    # the compilation model.
    model_state: Any = struct.field(default_factory=dict)


@dataclass
class TrainerConfig:
    learning_rate: float = 0.01
    # Pass train=True/False to model.apply (models with dropout/BN need it).
    has_train_arg: bool = False
    optimizer: str = "momentum"  # sgd | momentum | adamw | lamb | adafactor
    momentum: float = 0.9
    weight_decay: float = 0.0
    strategy: str = "dp"  # dp | fsdp
    # XLA lowers f32 matmuls/convs to bf16 MXU passes by default on TPU;
    # small f32 models can stall at init loss under that precision.  Set
    # "float32" (or "tensorfloat32") to pin it; None keeps the XLA default
    # (right for explicitly-bf16 large models).
    matmul_precision: str | None = None
    bf16_compute: bool = False
    remat: bool = False
    # Per-channel (mean, std) in the /255 domain for uint8 image inputs.
    # When set, normalization runs INSIDE the jitted step (XLA fuses it
    # into the first conv) instead of on the host: measured on this repo's
    # loader, host-side float normalization caps the input pipeline at
    # ~400 imagenet-rec/s/core while the uint8 path sustains thousands
    # (docs/BENCH_NOTES.md) — and uint8 halves host->device bytes vs bf16.
    # Applied in front of EVERY loss (the default objective AND custom
    # loss_fn/stateful_loss_fn), so uint8 streams work for detection too.
    input_stats: tuple[tuple[float, ...], tuple[float, ...]] | None = None
    # On-device augmentation (train/augment.py DeviceAugment, or any
    # ``fn(step, x) -> x``): composed in front of the loss inside the
    # jitted TRAIN step, seeded by fold_in(seed, state.step) — host
    # producers only decode and batch; flip/crop run on-chip, BEFORE the
    # in-step normalization (so uint8 stays uint8 across PCIe and the
    # pad-then-crop zeros match the host recipe's pre-normalize padding).
    # Eval never augments.
    augment: Any | None = None
    grad_clip_norm: float | None = None
    label_smoothing: float = 0.0
    lr_schedule: optax.Schedule | None = None
    log_every: int = 10
    # Gradient accumulation: the step's batch is split into this many
    # microbatches, gradients are averaged across them inside ONE
    # compiled step (lax.scan), and the optimizer updates once — a
    # batch-size-for-wallclock trade that fits effective batches the
    # chip's HBM cannot hold in one activation footprint.  Microbatches
    # are STRIDED slices (x[a::k]) so each one spans every data shard;
    # contiguous chunks would leave most devices idle per microbatch.
    # Distinct from Trainer.multi_step_fn(k): that is k optimizer
    # updates per dispatch, this is one update from k part-gradients.
    #
    # Averaging caveat: gradients are averaged UNIFORMLY across the k
    # microbatches (mean of per-microbatch means).  For losses normalized
    # by a per-batch COUNT rather than the batch size — MLM loss over
    # non-pad mask tokens, detection loss over matched boxes — that is an
    # approximation: the exact global mean would weight each microbatch
    # by its count.  Strided microbatch slices keep the counts near-equal
    # in expectation, so the bias is small; it is exactly zero for
    # fixed-denominator losses (LM next-token, classification).  See
    # docs/BENCH_NOTES.md ("grad-accum and count-normalized losses").
    grad_accum_steps: int = 1
    # The comms-overlap engine (parallel/overlap.py): replace GSPMD's
    # end-of-backward monolithic gradient sync with deterministic,
    # path-sorted, size-targeted buckets lowered as explicit collectives
    # inside shard_map — with grad accumulation, microbatch k+1's
    # backward pass overlaps bucket k's collective.  dp (replicated-
    # param) training is bit-identical to the monolithic path; fsdp is
    # numerically equivalent but not bitwise (GSPMD picks a different
    # backward factorization there).  Requires stateless models (no
    # BatchNorm collections) and a batch sharded on dim 0 over the data
    # axes only.  The audit ratchets the resulting schedule's
    # overlap_score (DLC512) — docs/PERFORMANCE.md, "Hiding the
    # collectives".
    comms_overlap: bool = False
    # Fused-bucket byte target for the overlap planner; smaller buckets
    # issue earlier (more overlap), larger ones amortize per-collective
    # latency better.
    overlap_bucket_bytes: int = 4 * 1024 * 1024
    # int8 gradient compression over the fused (replicated) buckets:
    # per-bucket symmetric quantization with an error-feedback residual
    # carried in the optimizer state (~4x wire-byte cut on the dp
    # all-reduce).  Changes numerics — convergence-gated in tests, off
    # by default.
    overlap_compress: bool = False


def decay_mask(params: Any) -> Any:
    """The canonical weight-decay mask: decay only conv/dense kernels.
    Norm scales and every bias are excluded — decaying a BatchNorm scale
    toward zero fights the normalization itself, and the standard 90-epoch
    ResNet-50 recipe (the one the reference delegated to tensorpack/MXNet,
    run.sh:92-93) excludes them.

    Rank >= 2 is the base rule (norm scales and biases are rank 1 in any
    plain Flax module tree), but rank alone is NOT sufficient for
    scan-stacked parameter trees: the llama family stores per-layer norm
    scales as one [L, d] rank-2 array (models/llama.py init_params), which
    a pure rank test would decay.  So paths whose leaf name marks them as
    norm/bias parameters are excluded at ANY rank.

    The name match is ANCHORED on '_'-separated components ('final_norm',
    'attn_norm', 'bias', 'scale'), never a substring test: 'norm' in
    'normalizer_proj' would silently exempt an unrelated projection kernel
    from decay (DLC005)."""

    _EXCLUDED = ("norm", "bias", "scale")

    def rule(path, p) -> bool:
        leaf = str(getattr(path[-1], "key", getattr(path[-1], "name", path[-1]))).lower()
        if leaf in _EXCLUDED or leaf.rsplit("_", 1)[-1] in _EXCLUDED:
            return False
        return p.ndim > 1

    return jax.tree_util.tree_map_with_path(rule, params)


def _make_optimizer(cfg: TrainerConfig) -> optax.GradientTransformation:
    lr = cfg.lr_schedule if cfg.lr_schedule is not None else cfg.learning_rate
    if cfg.optimizer == "sgd":
        tx = optax.sgd(lr)
    elif cfg.optimizer == "momentum":
        tx = optax.sgd(lr, momentum=cfg.momentum, nesterov=True)
    elif cfg.optimizer == "adamw":
        tx = optax.adamw(lr, weight_decay=cfg.weight_decay, mask=decay_mask)
    elif cfg.optimizer == "lamb":
        tx = optax.lamb(lr, weight_decay=cfg.weight_decay, mask=decay_mask)
    elif cfg.optimizer == "adafactor":
        # The memory-lean rung of the large-model ladder: factored second
        # moments (O(rows+cols) per matrix instead of O(rows*cols)) and no
        # first moment — the optimizer-state term that caps adamw at
        # ~1.1B params on a 16 GiB chip nearly vanishes.
        #
        # Decay-semantics translation: optax.adafactor applies
        # weight_decay_rate RAW per step (after LR scaling), while
        # adamw/lamb apply lr * wd — a config value tuned for adamw
        # (e.g. 0.1 at lr 3e-4) would decay ~1/lr-times stronger under
        # adafactor and collapse the weights.  Map to the adamw-effective
        # magnitude at the base LR so TrainerConfig.weight_decay means
        # one thing across optimizers.  (With an LR schedule, adamw's
        # effective decay tracks the schedule while this stays at the
        # base-LR value — a documented, conservative approximation.)
        tx = optax.adafactor(
            lr,
            weight_decay_rate=(
                cfg.weight_decay * cfg.learning_rate
                if cfg.weight_decay
                else None
            ),
            weight_decay_mask=decay_mask,
        )
    else:
        raise ValueError(f"unknown optimizer {cfg.optimizer!r}")
    chain = []
    if cfg.grad_clip_norm:
        chain.append(optax.clip_by_global_norm(cfg.grad_clip_norm))
    if cfg.weight_decay and cfg.optimizer in ("sgd", "momentum"):
        # L2-into-momentum, the classic SGD form: the decay term joins the
        # gradient BEFORE the momentum integrator and the lr scaling —
        # exactly what "weight decay 1e-4" means in the canonical ResNet
        # recipe.  adamw/lamb/adafactor carry decoupled decay internally.
        chain.append(optax.add_decayed_weights(cfg.weight_decay, mask=decay_mask))
    chain.append(tx)
    return optax.chain(*chain) if len(chain) > 1 else tx


def _accumulated_grads(loss_fn, state, x, y, accum: int):
    """Mean loss/aux/gradients over ``accum`` strided microbatches,
    computed by one lax.scan so only a single microbatch's activations
    are ever live.  Microbatch ``a`` is ``leaf[a::accum]`` — the strided
    view keeps every data shard populated in every microbatch (a
    contiguous split would park whole microbatches on a subset of
    devices).  BatchNorm-style collections thread through sequentially,
    exactly as they would across real steps."""

    def to_micro(leaf):
        n = leaf.shape[0]
        if n % accum:
            raise ValueError(
                f"batch axis {n} not divisible by grad_accum_steps={accum}"
            )
        # leaf[a::accum] == reshape(n//accum, accum, ...)[:, a]; moving
        # the accum axis first gives scan its [accum, micro, ...] xs.
        return jnp.swapaxes(
            leaf.reshape((n // accum, accum) + leaf.shape[1:]), 0, 1
        )

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def body(carry, xy):
        grads_acc, model_state = carry
        x_m, y_m = xy
        (loss, (aux, model_state)), grads = grad_fn(
            state.params, model_state, x_m, y_m
        )
        grads_acc = jax.tree_util.tree_map(jnp.add, grads_acc, grads)
        return (grads_acc, model_state), (loss, aux)

    # The step's `loss` scope (see _raw_step_fn): the microbatch views and
    # the running sum ride with the pass they serve.
    with jax.named_scope("loss"):
        xs = jax.tree_util.tree_map(to_micro, x)
        ys = jax.tree_util.tree_map(to_micro, y)
        zeros = jax.tree_util.tree_map(jnp.zeros_like, state.params)
        (grads_sum, new_model_state), (losses, auxes) = jax.lax.scan(
            body, (zeros, state.model_state), (xs, ys)
        )
        grads = jax.tree_util.tree_map(lambda g: g / accum, grads_sum)
        aux = jax.tree_util.tree_map(lambda v: jnp.mean(v, axis=0), auxes)
        return jnp.mean(losses), aux, new_model_state, grads


def _fold_counters(pending: list[dict[str, jax.Array]]) -> None:
    """Each pending step's ``metrics["counters"]`` into ``obs.tracing``'s
    counters (one observation per step and name), and the list emptied."""
    for step_counters in jax.device_get(pending):
        for name, value in step_counters.items():
            counter(name, float(value))
    pending.clear()


def softmax_xent(logits: jax.Array, labels: jax.Array, smoothing: float = 0.0) -> jax.Array:
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logits.dtype)
    if smoothing:
        onehot = onehot * (1.0 - smoothing) + smoothing / num_classes
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.sum(onehot.astype(jnp.float32) * logp, axis=-1))


class _FitSeams:
    """The seams of the training loop, once, for ``fit`` and ``_fit_multi``.

    A seam is an ``obs.tracing.span`` that is not journalled: it folds into
    the per-name aggregate, is kept among the recent spans and annotates a
    ``jax.profiler`` capture, so that what the host was doing lies beside
    the device's operations under a name.  Everything the training thread
    does between two dispatches lies under one of them:

    - ``fit.step``: one iteration, ``step_num`` the global step it
      dispatches; the one seam that is journalled, as ``train_step``, the
      name the journal's readers know.  It times the host's side of the
      iteration, not the step on the device.
    - ``fit.data_wait``: ``next()`` on the batch source.
    - ``fit.h2d``: ``device_put_tree`` (a prefetched batch moves nothing).
    - ``fit.dispatch``: the call of the jitted step, which returns when the
      program is enqueued.
    - ``fit.sync``: the host waits for the device: the first step's
      ``block_until_ready`` and the ``device_get`` of the pending losses
      every ``log_every`` steps.
    - ``fit.log``: the per-step hooks: the logger, the checkpointer's
      ``should_save``, ``stop_fn``.
    - ``fit.checkpoint``: a save; journalled as ``checkpoint``.

    Where a ``fit.sync`` that drained the pending losses ends, the device's
    queue is empty: what the thread does from there to the end of the next
    ``fit.dispatch`` the device waits out one for one.  ``drain`` and
    ``dispatched`` stamp the two ends into a row of ``obs.tracing``'s
    ``recent_drains()`` (``Drains``); the first step's wait is no drain.
    A drain far slower than the loop's last ones is journalled once as a
    ``stall`` (``_judge``), with the seam that held most of the host's
    segment inside it and, under ``during``, whatever else the thread did
    under a name in that time: a ``fit.checkpoint``, a ``reshard``.

    A ``StepProfiler``, when one was passed, gets the same seconds folded
    into its phases (``PHASES``), so its API and its readers stay as they
    were; its ``compute`` is the host's wait at a sync point, a lower
    bound on device time.
    """

    PHASES = {
        "fit.data_wait": "data_wait",
        "fit.h2d": "h2d",
        "fit.dispatch": "dispatch",
        "fit.sync": "compute",
    }
    _END = object()
    #: The seams of one iteration: any other span of the thread inside a slow
    #: drain's interval is a pause with a name of its own.
    STEP_SEAMS = frozenset(
        ("fit.step", "fit.data_wait", "fit.h2d", "fit.dispatch", "fit.sync", "fit.log")
    )
    #: A drain is a stall when its seconds per step exceed the median of the
    #: last ``STALL_WINDOW`` drains by both: the factor keeps a slow model's
    #: jitter out, the floor a fast model's (half again of 5 ms is nothing an
    #: operator can act on; 50 ms is a tenth of a percent of a minute).
    STALL_FACTOR = 1.5
    STALL_FLOOR_S = 0.05
    STALL_WINDOW = 64
    #: With fewer drains behind it the median is the loop's first, unsettled steps.
    STALL_MIN_DRAINS = 8

    def __init__(self, trainer: "Trainer", profiler: Any):
        from deeplearning_cfn_tpu.obs.profiler import NULL_PROFILER

        self.trainer = trainer
        self.prof = profiler if profiler is not None else NULL_PROFILER
        self.t_fit = time.perf_counter()
        self.first_done = False
        self.drains = Drains()
        self._per_step: collections.deque[float] = collections.deque(maxlen=self.STALL_WINDOW)

    def __call__(self, name: str, samples: int = 1):
        """The context for one seam; ``samples`` spreads a sync's seconds
        over the steps it drained, for the profiler."""
        phase = self.PHASES.get(name)
        if phase is None or not self.prof.enabled:
            return span(name, journal=False)
        return self._profiled(name, phase, samples)

    @contextlib.contextmanager
    def _profiled(self, name: str, phase: str, samples: int):
        t0 = time.perf_counter()
        try:
            with span(name, journal=False):
                yield
        finally:
            self.prof.fold(phase, time.perf_counter() - t0, samples=samples)

    @contextlib.contextmanager
    def drain(self, gstep: int, steps: int):
        """``fit.sync`` around the readback of ``steps`` pending steps, the
        last of them ``gstep``, and the drain's row where it ends."""
        before = self.drains.last
        with self("fit.sync", steps):  # the profiler spreads over at least one
            yield
        row = self.drains.returned(gstep, steps)
        if row is not None and before is not None:
            self._judge(row, before)

    def dispatched(self) -> None:
        """Where a ``fit.dispatch`` has ended: behind a drain, the end of its
        exposed segment; on every other step one attribute check."""
        if self.drains.open is not None:
            self.drains.dispatched()

    def _judge(self, row: dict[str, Any], before: dict[str, Any]) -> None:
        """Journal the drain as a ``stall`` if it is one.  The host's segment
        inside its interval is the one the drain ``before`` opened."""
        per_step = row["interval_s"] / row["steps"]
        recent = self._per_step
        if per_step < self.STALL_FLOOR_S or len(recent) < self.STALL_MIN_DRAINS:
            recent.append(per_step)  # under the floor it is over no median by it
            return
        median = sorted(recent)[len(recent) // 2]
        recent.append(per_step)
        if per_step <= self.STALL_FACTOR * median or per_step - median < self.STALL_FLOOR_S:
            return
        thread, start_ns = row["thread"], before["sync_end_ns"]
        seam = None
        if before["exposed_s"] is not None:
            held = spans_between(thread, start_ns, start_ns + int(before["exposed_s"] * 1e9))
            held.pop("host.gc", None)  # inside the seam that ran it, and in ``exposed_gc_s``
            # the iteration's own span holds the others: what they leave is its alone
            held["fit.step"] = before["exposed_s"] - sum(
                v for n, v in held.items() if n != "fit.step"
            )
            seam = max(held, key=held.get)
        during = sorted(
            set(spans_between(thread, start_ns, row["sync_end_ns"])) - self.STEP_SEAMS - {"host.gc"}
        )
        get_recorder().record(
            "stall",
            **{k: row[k] for k in ("step", "steps", "sync_end_ns", "interval_s", "gc_s", "nivcsw", "majflt")},
            median_step_s=median, excess_s=row["interval_s"] - row["steps"] * median,
            exposed_s=before["exposed_s"], exposed_gc_s=before["exposed_gc_s"],
            seam=seam, during=during or None,
        )

    def step(self, gstep: int):
        return span("fit.step", journal="train_step", step_num=gstep)

    def checkpoint(self, gstep: int):
        return span("fit.checkpoint", journal="checkpoint", step=gstep)

    def source(self, batches):
        """``batches`` with every ``next()`` under ``fit.data_wait``."""
        it = iter(batches)
        while True:
            with self("fit.data_wait"):
                item = next(it, self._END)
            if item is self._END:
                return
            yield item

    def first_step(self, value: Any) -> None:
        """Once per fit, after the first dispatch: wait for it and stamp
        time-to-first-step (compile included), one half of the driver's
        template-to-first-step metric; the wait doubles as the compile's
        completion.  The compile counters are frozen under ``first_step.``
        here, apart from what the process compiles afterwards."""
        if self.first_done:
            return
        self.first_done = True
        with self("fit.sync"):
            jax.block_until_ready(value)
        now = time.perf_counter()
        self.trainer.first_step_seconds = now - self.t_fit
        self.trainer.first_step_at = now
        freeze_counters("compile.", "first_step.")


class Trainer:
    """Builds and runs the jitted SPMD train step for a Flax model.

    ``loss_fn(params, x, y) -> (loss, aux)`` may be supplied for custom
    objectives; the default is softmax cross-entropy classification.
    Models with mutable collections (BatchNorm) and a custom objective use
    ``stateful_loss_fn(params, model_state, x, y) ->
    (loss, (aux, new_model_state))`` instead.  ``y`` may be any pytree whose
    leaves lead with the batch axis (detection targets are dicts).
    """

    def __init__(
        self,
        model: Any,
        mesh: Mesh,
        config: TrainerConfig,
        loss_fn: Callable[[Any, jax.Array, jax.Array], tuple[jax.Array, dict]] | None = None,
        param_shardings: Any = None,
        batch_spec: P | None = None,
        stateful_loss_fn: Callable[..., tuple[jax.Array, tuple[dict, Any]]] | None = None,
        eval_loss_fn: Callable[..., tuple[jax.Array, dict]] | None = None,
        analytic_flops_fn: Callable[[jax.Array], float] | None = None,
    ):
        self.model = model
        self.mesh = mesh
        self.config = config
        self.tx = _make_optimizer(config)
        self._custom_loss = loss_fn
        self._custom_stateful_loss = stateful_loss_fn
        # analytic_flops_fn(global_batch_x) -> GLOBAL train flops per step.
        # Models whose hot path runs inside Pallas custom calls (flash
        # attention) MUST supply this: XLA cost analysis cannot see
        # custom-call FLOPs, so every cost-analysis consumer would silently
        # under-report MFU (docs/BENCH_NOTES.md).  compile_stats and
        # throughput_logger prefer it whenever present.
        self.analytic_flops_fn = analytic_flops_fn
        # eval_loss_fn(params, model_state, x, y) -> (loss, metrics): the
        # eval-mode counterpart of a custom stateful loss (train=False,
        # no mutation).
        self._custom_eval_loss = eval_loss_fn
        self._explicit_param_shardings = param_shardings
        # Images: [B, ...] split over the data axes.  Token models pass
        # P(("dp","fsdp"), "sp") to also shard the sequence axis.
        self.batch_sharding = NamedSharding(
            mesh, batch_spec if batch_spec is not None else P(("dp", "fsdp"))
        )
        self._step_fn = None
        self.state_shardings: TrainState | None = None
        # Set by fit(): wallclock from fit entry to the first completed
        # step (compile included), and the absolute perf_counter timestamp
        # of that completion (lets callers measure from their own start,
        # covering data/loader/init setup that precedes fit).
        self.first_step_seconds: float | None = None
        self.first_step_at: float | None = None
        # Set by fit(): bytes of the first batch's shards per device id,
        # as placed for the step — the input reached every chip.
        self.batch_bytes_by_device: dict[int, int] | None = None

    # --- loss -----------------------------------------------------------
    def _normalize_input(self, x: jax.Array) -> jax.Array:
        """In-step uint8 normalization (config.input_stats); float inputs
        pass through untouched so synthetic/pre-normalized paths are
        unchanged.  Delegates to the ONE shared implementation
        (train/pipeline.dequantize_normalize) so the on-device path can
        never drift from the host-side datasets.normalize_images."""
        stats = self.config.input_stats
        if stats is None or x.dtype != jnp.uint8:
            return x
        from deeplearning_cfn_tpu.train.pipeline import dequantize_normalize

        return dequantize_normalize(x, stats[0], stats[1])

    def _default_objective(
        self, params: Any, model_state: Any, x: jax.Array, y: jax.Array, train: bool
    ) -> tuple[jax.Array, dict, Any]:
        """The default classification objective, shared by the train and
        eval steps so their metrics stay numerically comparable.  Eval
        (train=False) disables dropout, reads BatchNorm running stats, and
        never mutates collections."""
        x = self._normalize_input(x)
        if self.config.bf16_compute:
            x = x.astype(jnp.bfloat16)
        variables = {"params": params, **model_state}
        kwargs = {"train": train} if self.config.has_train_arg else {}
        mutable = list(model_state.keys()) if train else []
        if mutable:
            logits, new_model_state = self.model.apply(
                variables, x, mutable=mutable, **kwargs
            )
        else:
            logits = self.model.apply(variables, x, **kwargs)
            new_model_state = model_state
        with jax.named_scope("xent"):
            loss = softmax_xent(logits, y, self.config.label_smoothing)
            acc = jnp.mean((jnp.argmax(logits, -1) == y).astype(jnp.float32))
        return loss, {"accuracy": acc}, new_model_state

    def _loss(
        self, params: Any, model_state: Any, x: jax.Array, y: jax.Array
    ) -> tuple[jax.Array, tuple[dict, Any]]:
        if self._custom_stateful_loss is not None:
            return self._custom_stateful_loss(params, model_state, x, y)
        if self._custom_loss is not None:
            loss, aux = self._custom_loss(params, x, y)
            return loss, (aux, model_state)
        loss, aux, new_model_state = self._default_objective(
            params, model_state, x, y, train=True
        )
        return loss, (aux, new_model_state)

    # --- init -----------------------------------------------------------
    @span("trainer.init")
    def init(self, rng: jax.Array, sample_x: jax.Array) -> TrainState:
        """Initialize params/opt-state and place them on the mesh."""
        init_kwargs = {"train": False} if self.config.has_train_arg else {}

        # The model sees what the train step feeds it: the augment stage
        # runs first (margin records crop stored-size inputs down to the
        # model size — models with flatten heads need the cropped shape
        # at init), then uint8 batches (input_stats) normalize in-step.
        # Composed INSIDE the traced init (and inside eval_shape below),
        # never eagerly: an eager slice/dequantize here dispatches tiny
        # one-off programs that read as retraces in the bench's compile
        # watcher.  The sample aval is built symbolically for the same
        # reason.
        def _prep(sample):
            sample = sample[:1]
            if self.config.augment is not None:
                sample = self.config.augment(jnp.zeros((), jnp.int32), sample)
            return self._normalize_input(sample)

        sample_aval = jax.ShapeDtypeStruct(tuple(sample_x.shape), sample_x.dtype)
        variables = jax.eval_shape(
            lambda r, s: self.model.init(r, _prep(s), **init_kwargs), rng, sample_aval
        )
        abstract_params = variables["params"]
        abstract_model_state = {k: v for k, v in variables.items() if k != "params"}
        if self._explicit_param_shardings is not None:
            param_sh = self._explicit_param_shardings
        elif self.config.strategy == "fsdp":
            param_sh = infer_param_sharding(abstract_params, self.mesh)
        else:
            param_sh = jax.tree_util.tree_map(
                lambda _: replicated(self.mesh), abstract_params
            )
        opt_sh = self._opt_state_shardings(abstract_params, param_sh)
        # Compressed overlap carries per-bucket error-feedback residuals
        # in the opt state (parallel/overlap.ErrorFeedbackState), so the
        # state tree — and its shardings — grow a wrapper here.
        overlap_plan = None
        if self.config.comms_overlap and self.config.overlap_compress:
            sync_axes = self._overlap_sync_axes()
            nd = 1
            for a in sync_axes:
                nd *= self.mesh.shape[a]
            overlap_plan = plan_buckets(
                abstract_params,
                jax.tree_util.tree_map(lambda s: s.spec, param_sh),
                self.config.overlap_bucket_bytes,
            )
            opt_sh = ErrorFeedbackState(
                residual=error_feedback_shardings(
                    overlap_plan, self.mesh, sync_axes
                ),
                inner=opt_sh,
            )
        model_state_sh = jax.tree_util.tree_map(
            lambda _: replicated(self.mesh), abstract_model_state
        )
        self.state_shardings = TrainState(
            step=replicated(self.mesh),
            params=param_sh,
            opt_state=opt_sh,
            model_state=model_state_sh,
        )

        @partial(jax.jit, out_shardings=self.state_shardings)
        def _init(rng, sample):
            variables = self.model.init(rng, _prep(sample), **init_kwargs)
            params = variables["params"]
            model_state = {k: v for k, v in variables.items() if k != "params"}
            opt_state = self.tx.init(params)
            if overlap_plan is not None:
                opt_state = init_error_feedback(overlap_plan, nd, opt_state)
            return TrainState(
                step=jnp.zeros((), jnp.int32),
                params=params,
                opt_state=opt_state,
                model_state=model_state,
            )

        return _init(rng, sample_x)

    def _opt_state_shardings(
        self, abstract_params: Any, param_sh: Any, mesh: Mesh | None = None
    ) -> Any:
        """Optimizer state mirrors parameter sharding (moments are
        param-shaped); everything else (step counts, EMA scalars) is
        replicated.

        The mapping is PATH-aligned via ``optax.tree_map_params``, never
        by shape: two params with the same shape but different layouts
        (llama's ``wq`` P(None,fsdp,tp) vs ``wo`` P(None,tp,fsdp) — both
        [L,D,D] at MHA shapes) must each get their OWN sharding for their
        adam moments, or XLA silently inserts resharding collectives on
        the moments every step."""
        opt_shape = jax.eval_shape(self.tx.init, abstract_params)
        rep = replicated(mesh if mesh is not None else self.mesh)
        return optax.tree_map_params(
            self.tx,
            # Shape guard: factored-optimizer leaves (adafactor's
            # v_row/v_col, O(rows+cols) each) are param-ALIGNED but not
            # param-SHAPED; forcing the param's sharding onto them would
            # be ill-ranked.  They are small — replicate them.
            lambda leaf, sh, p: sh if getattr(leaf, "shape", None) == p.shape else rep,
            opt_shape,
            param_sh,
            abstract_params,
            transform_non_params=lambda _leaf: rep,
        )

    def _overlap_sync_axes(self) -> tuple[str, ...]:
        """The mesh axes the comms-overlap engine syncs gradients over —
        the axes the batch's leading dim is sharded on (full validation
        happens in parallel/overlap._resolve_sync_axes)."""
        spec = self.batch_sharding.spec
        dim0 = spec[0] if spec else None
        if dim0 is None:
            raise ValueError(
                "comms_overlap needs the batch sharded on dim 0; got "
                f"batch spec {spec}"
            )
        return (dim0,) if isinstance(dim0, str) else tuple(dim0)

    def rebind_mesh(self, mesh: Mesh, state_shardings: TrainState) -> None:
        """Point the trainer at a new mesh with a matching sharding
        template — the live-reshard seam (train/reshard.py).  The batch
        spec is preserved (same axis names; our meshes always carry every
        named axis, sized 1 where unused), and the cached jitted step and
        eval functions are dropped so the next call recompiles against
        the new topology.  The caller is responsible for having migrated
        the actual TrainState onto ``state_shardings`` first."""
        self.mesh = mesh
        self.batch_sharding = NamedSharding(mesh, self.batch_sharding.spec)
        self.state_shardings = state_shardings
        self._step_fn = None
        self._eval_fn = None

    # --- the step -------------------------------------------------------
    def _overlap_grads(self, loss_fn, state: TrainState, x, y, accum: int):
        """Trace-time dispatch into the comms-overlap engine: plan the
        buckets from the (traced) parameter tree's shapes and lower the
        loss/grad/sync step through parallel/overlap.py.  Runs inside
        the jitted step, so the plan and the shard_map are rebuilt once
        per compile — never per step."""
        if state.model_state:
            raise ValueError(
                "comms_overlap requires stateless models (no mutable "
                "collections such as BatchNorm stats); got model_state "
                f"keys {sorted(state.model_state)}"
            )
        assert self.state_shardings is not None, "call init() before the step"
        param_specs = jax.tree_util.tree_map(
            lambda s: s.spec, self.state_shardings.params
        )
        plan = plan_buckets(
            state.params, param_specs, self.config.overlap_bucket_bytes
        )
        compress = self.config.overlap_compress
        fn = build_overlap_grad_fn(
            loss_fn,
            self.mesh,
            param_specs,
            self.batch_sharding.spec,
            plan,
            accum=accum,
            compress=compress,
        )
        residuals = state.opt_state.residual if compress else ()
        with jax.named_scope("loss"):
            return fn(state.params, x, y, residuals)

    def _raw_step_fn(self):
        """The unjitted single-step body, shared by the jitted step and
        the multi-step scan so their semantics cannot drift."""
        loss_fn = self._loss
        if self.config.remat:
            loss_fn = jax.checkpoint(loss_fn)

        precision = self.config.matmul_precision

        accum = self.config.grad_accum_steps
        if accum < 1:
            raise ValueError(f"grad_accum_steps must be >= 1, got {accum}")

        augment = self.config.augment
        overlap = self.config.comms_overlap
        compress = self.config.overlap_compress

        # Named `train_step`: a profile's `XLA Modules` line and the
        # compile cache's entry read `jit_train_step`.
        def train_step(state: TrainState, x: jax.Array, y: jax.Array):
            ctx = (
                jax.default_matmul_precision(precision)
                if precision
                else contextlib.nullcontext()
            )
            with ctx:
                # The device-resident input stage, fused into the step:
                # seeded augmentation (keyed by the training step, so the
                # transform is resume-stable and prefetch-depth-invariant)
                # then uint8 dequantize+normalize — custom losses receive
                # float inputs exactly like the default objective
                # (_normalize_input is a no-op for float x, so the
                # default objective's own call cannot double-normalize).
                #
                # Three named scopes split the step's device time in a
                # profile (metadata only; the computation is the same):
                # `input`, `loss` (JAX itself marks the backward half
                # `transpose(jvp(...))` and a rematerialised forward
                # `rematted_computation`/`checkpoint` inside it) and
                # `optimizer`.  Every gradient path carries all three.
                with jax.named_scope("input"):
                    if augment is not None:
                        x = augment(state.step, x)
                    x = self._normalize_input(x)
                if overlap:
                    loss, aux, grads, new_residuals = self._overlap_grads(
                        loss_fn, state, x, y, accum
                    )
                    new_model_state = state.model_state
                elif accum == 1:
                    with jax.named_scope("loss"):
                        (loss, (aux, new_model_state)), grads = jax.value_and_grad(
                            loss_fn, has_aux=True
                        )(state.params, state.model_state, x, y)
                else:
                    loss, aux, new_model_state, grads = _accumulated_grads(
                        loss_fn, state, x, y, accum
                    )
            metrics = {"loss": loss, **aux}
            with jax.named_scope("optimizer"):
                if overlap and compress:
                    updates, new_inner = self.tx.update(
                        grads, state.opt_state.inner, state.params
                    )
                    new_opt = ErrorFeedbackState(
                        residual=new_residuals, inner=new_inner
                    )
                else:
                    updates, new_opt = self.tx.update(
                        grads, state.opt_state, state.params
                    )
                new_params = optax.apply_updates(state.params, updates)
            new_state = TrainState(
                step=state.step + 1,
                params=new_params,
                opt_state=new_opt,
                model_state=new_model_state,
            )
            return new_state, metrics

        return train_step

    def _build_step(self):
        assert self.state_shardings is not None, "call init() before train_step"
        return jax.jit(
            self._raw_step_fn(),
            in_shardings=(self.state_shardings, self.batch_sharding, self.batch_sharding),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,),
        )

    def multi_step_fn(self, k: int):
        """One compiled program executing ``k`` consecutive train steps
        (lax.scan over batches stacked on a leading [k] axis) — the only
        expressible form of cross-iteration fusion under XLA: separate
        dispatches are separate executables, so a compiler can only
        overlap or reuse across an iteration boundary when both
        iterations live in ONE module.  Returns a jitted
        ``(state, xs[k,B,...], ys[k,...]) -> (state, losses[k])``.

        Measured at the ResNet-50 bench shape (docs/BENCH_NOTES.md r5):
        the candidate savings are param/optimizer re-reads, which are
        <1% of the step's HBM traffic — activation bytes dominate and
        are batch-unique, so no cross-iteration reuse exists for them.
        The real win is on the HOST side: one dispatch (and one
        pre-staged input stack) per k steps.  ``fit(steps_per_call=k)``
        feeds this program double-buffered device-resident stacks and
        frees each consumed stack right after dispatch
        (docs/PERFORMANCE.md, "the overlap architecture").
        """
        if k < 1:
            raise ValueError(f"k must be >= 1, got {k}")
        raw = self._raw_step_fn()

        def k_steps(state: TrainState, xs: jax.Array, ys: jax.Array):
            def body(st, xy):
                st, metrics = raw(st, xy[0], xy[1])
                return st, metrics["loss"]

            state, losses = jax.lax.scan(body, state, (xs, ys))
            return state, losses

        assert self.state_shardings is not None, "call init() before multi_step_fn"
        stacked = NamedSharding(
            self.mesh, P(None, *self.batch_sharding.spec)
        )
        return jax.jit(
            k_steps,
            in_shardings=(self.state_shardings, stacked, stacked),
            out_shardings=(self.state_shardings, None),
            donate_argnums=(0,),
        )

    @property
    def step_fn(self):
        if self._step_fn is None:
            self._step_fn = self._build_step()
        return self._step_fn

    def train_step(self, state: TrainState, x: jax.Array, y: jax.Array):
        # Mesh context makes bare-PartitionSpec sharding hints inside model
        # code (e.g. llama._maybe_shard) resolvable during tracing.
        with jax.set_mesh(self.mesh):
            return self.step_fn(state, x, y)

    # --- evaluation -------------------------------------------------------
    def _build_eval_step(self):
        def eval_loss(params, model_state, x, y):
            if self._custom_eval_loss is not None:
                return self._custom_eval_loss(params, model_state, x, y)
            if self._custom_stateful_loss is not None:
                # No eval variant supplied: the custom loss applies the
                # model however it was written (usually train mode), so
                # these metrics carry train-mode semantics.
                log.warning(
                    "evaluate() with a stateful loss and no eval_loss_fn "
                    "runs the model in train mode; pass eval_loss_fn for "
                    "true eval semantics"
                )
                loss, (aux, _) = self._custom_stateful_loss(params, model_state, x, y)
                return loss, aux
            if self._custom_loss is not None:
                return self._custom_loss(params, x, y)
            loss, aux, _ = self._default_objective(
                params, model_state, x, y, train=False
            )
            return loss, aux

        precision = self.config.matmul_precision

        def eval_fn(state: TrainState, x: jax.Array, y: jax.Array):
            # Same matmul precision as the train step: eval metrics must be
            # comparable to the train metrics they sit next to.
            ctx = (
                jax.default_matmul_precision(precision)
                if precision
                else contextlib.nullcontext()
            )
            with ctx:
                # uint8 eval streams dequantize in-step like training,
                # including for custom losses; augmentation is train-only.
                x = self._normalize_input(x)
                loss, aux = eval_loss(state.params, state.model_state, x, y)
            return {"loss": loss, **aux}

        assert self.state_shardings is not None, "call init() before evaluate"
        return jax.jit(
            eval_fn,
            in_shardings=(self.state_shardings, self.batch_sharding, self.batch_sharding),
        )

    @property
    def eval_step(self):
        if getattr(self, "_eval_fn", None) is None:
            self._eval_fn = self._build_eval_step()
        return self._eval_fn

    def _batch_axis_shards(self) -> int:
        """How many ways the leading (batch) axis is split on the mesh —
        the divisibility requirement for any batch fed to the jitted
        steps."""
        spec = self.batch_sharding.spec
        if not spec or spec[0] is None:
            return 1
        axes = spec[0] if isinstance(spec[0], tuple) else (spec[0],)
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        return n

    def _trim_to_shards(self, x, y):
        """Full-split eval passes yield one final partial batch
        (drop_remainder=False).  GSPMD requires the leading axis to
        divide by the batch-shard count; when the tail doesn't, trim it
        to the largest divisible size — LOUDLY, because the dropped
        examples shrink the claimed split.  Returns (x, y, kept)."""
        n = len(x)
        div = self._batch_axis_shards()
        if n % div == 0:
            return x, y, n
        keep = (n // div) * div
        log.warning(
            "eval tail batch of %d examples is not divisible by the %d "
            "batch shards; dropping %d examples — size the eval batch to "
            "divide the split for a complete pass", n, div, n - keep,
        )
        if keep == 0:
            return None, None, 0
        trim = lambda a: a[:keep]
        return (
            jax.tree_util.tree_map(trim, x),
            jax.tree_util.tree_map(trim, y),
            keep,
        )

    def evaluate(
        self,
        state: TrainState,
        batches,
        steps: int | None = None,
        prefetch: int = 2,
    ) -> dict:
        """Run the no-gradient eval step over a batch iterator and return
        example-weighted mean metrics (plus ``examples`` seen).  The held-
        out counterpart of the reference's train-accuracy walkthrough
        metric (README.md:141).  ``prefetch`` overlaps host batch
        production and transfer with eval compute, as in fit()."""
        from deeplearning_cfn_tpu.train.data import DevicePrefetcher

        eval_fn = self.eval_step
        # islice, not enumerate+break: break would pull (and discard) one
        # batch past the limit from the caller's iterator.
        if steps is not None:
            batches = itertools.islice(batches, steps)

        def trimmed(src):
            # Full-split passes (drop_remainder=False loaders) end with a
            # partial batch; make it mesh-divisible BEFORE the prefetcher
            # device_puts it.
            from deeplearning_cfn_tpu.train.data import Batch

            for b in src:
                x, y, kept = self._trim_to_shards(b.x, b.y)
                if kept:
                    yield Batch(x=x, y=y)

        batches = trimmed(batches)
        prefetcher: DevicePrefetcher | None = None
        if prefetch > 0:
            from deeplearning_cfn_tpu.train.pipeline import PipelineStats

            batches = prefetcher = DevicePrefetcher(
                batches,
                self.batch_sharding,
                prefetch,
                stats=PipelineStats(name="eval"),
            )
        # Device scalars accumulate host-side and materialize in ONE
        # readback at the end — a per-batch float() would serialize the
        # eval loop on device round-trips just like the old fit() did.
        per_batch: list[tuple[int, dict]] = []
        try:
            with span("eval"):
                for batch in batches:
                    # device_put_batch skips leaves the prefetcher already
                    # placed with an equivalent sharding.
                    x, y = device_put_batch(batch, self.batch_sharding)
                    with jax.set_mesh(self.mesh):
                        metrics = eval_fn(state, x, y)
                    per_batch.append((len(batch.x), metrics))
        finally:
            if prefetcher is not None:
                prefetcher.close()
        counts = [n for n, _ in per_batch]
        examples = sum(counts)
        if examples == 0:
            return {"examples": 0}
        materialized = jax.device_get([m for _, m in per_batch])
        totals: dict[str, float] = {}
        for n, metrics in zip(counts, materialized):
            for k, v in metrics.items():
                if isinstance(v, dict):  # counters, not a mean over examples
                    continue
                totals[k] = totals.get(k, 0.0) + float(v) * n
        out = {k: v / examples for k, v in totals.items()}
        out["examples"] = examples
        return out

    def _save_checkpoint(
        self, checkpointer: Any, step: int, state: TrainState, datastream: Any
    ) -> None:
        """One checkpoint save, with the data plane's position attached
        when both sides support it.  With ``prefetch > 0`` the stream's
        host-side cursor can run up to ``prefetch + 1`` batches ahead of
        the trained step (the buffer was filled ahead); runs that need
        bit-exact stream resume (chaos ``data-reshard-live``) use
        ``prefetch=0`` — docs/DATA.md quantifies the skew."""
        if datastream is not None and getattr(
            checkpointer, "accepts_stream_state", False
        ):
            stream_state = datastream.stream_state()
            if hasattr(stream_state, "to_json"):
                stream_state = stream_state.to_json()
            checkpointer.save(step, state, stream_state=stream_state)
        else:
            checkpointer.save(step, state)

    # --- convenience loop (the MonitoredTrainingSession analog) ----------
    def fit(
        self,
        state: TrainState,
        batches,
        steps: int,
        logger: ThroughputLogger | None = None,
        checkpointer: Any = None,
        stop_fn: Callable[[dict], bool] | None = None,
        prefetch: int = 2,
        prefetch_workers: int = 1,
        reshard: Any = None,
        profiler: Any = None,
        steps_per_call: int = 1,
        datastream: Any = None,
    ) -> tuple[TrainState, list[float]]:
        """``stop_fn(metrics) -> True`` ends training early — the
        time-to-accuracy mode (the reference's only published CIFAR metric
        is 100-epochs-to-92%-accuracy, README.md:141).

        The loop never reads a metric back to the host per step: a
        per-step ``float(loss)`` would serialize host and device and
        defeat XLA's async dispatch.  Device scalars are collected and
        materialized once at the end; the host blocks (and ``stop_fn``
        runs) only every ``config.log_every`` steps, which both bounds
        how far dispatch runs ahead of the device and sets the
        early-stop granularity (set ``log_every=1`` for per-step
        stopping).

        ``prefetch`` > 0 moves host-batch production and the
        host->device transfer onto a background thread, ``prefetch``
        batches ahead (train/data.py:DevicePrefetcher), so input IO
        overlaps compute; 0 = inline transfers.  ``prefetch_workers``
        > 1 adds parallel producer threads behind a reorder buffer
        (iteration order unchanged) for decode-bound sources.  In
        every mode at most ``steps`` batches are consumed from the
        caller's iterator (an early ``stop_fn`` exit may have pulled
        up to ``prefetch`` of those ahead without training on them).

        Pipeline counters for the run (bytes over PCIe, host input
        time, stall/wait split) land on ``self.last_pipeline_stats``
        and are journaled via the obs plane as an ``input_pipeline``
        event (docs/PERFORMANCE.md).

        ``reshard`` (a train/reshard.LiveReshardCoordinator, duck-typed)
        is the elastic pause/resume seam: at every step boundary the
        loop asks ``reshard.pending()``; when a coalesced slice loss is
        waiting it drains the in-flight device scalars (they reference
        the old mesh) and hands itself to ``reshard.execute``, which
        migrates the state device-to-device and rebinds this trainer to
        the surviving mesh.  ``"resume"`` continues on the SAME batch
        iterator with the recompiled step — no step is lost or repeated;
        ``"stop"`` (graceful degradation to the checkpoint/restore path)
        breaks out, returning the partial losses like an early stop_fn
        exit.  With a prefetcher, already-placed batches are simply
        re-put onto the new mesh by device_put_tree.

        The loop's seams are spans of ``obs.tracing`` (``_FitSeams``:
        ``fit.step`` around an iteration, ``fit.data_wait``, ``fit.h2d``,
        ``fit.dispatch``, ``fit.sync``, ``fit.log``, ``fit.checkpoint``
        inside it), which a ``jax.profiler`` capture shows beside the
        device's operations.  All of them time the HOST: ``fit.step``
        (journalled as ``train_step``) is the host's side of one
        iteration, not the step's time on the device.

        ``profiler`` (an obs.profiler.StepProfiler, default None = off)
        gets the seams' seconds folded into its data_wait / h2d /
        dispatch / compute / host phases; ``compute`` is the host's wait
        at the loop's existing sync points (amortized over the steps
        drained there), a lower bound on device time, so nothing about
        the dispatch pipeline changes when profiling is on.  NOTE: the
        first step's interval includes compile — read p50, not max, for
        steady-state.

        ``steps_per_call`` > 1 routes through ``multi_step_fn(k)``: k
        host batches are stacked host-side, prefetched device-resident
        as ONE pre-staged stack, dispatched as one scanned program, and
        the consumed stack's buffers are explicitly freed (donated)
        right after dispatch — the overlap architecture
        docs/PERFORMANCE.md describes.  Semantically identical to k
        single-step dispatches (tests pin bit-parity); incompatible
        with ``reshard`` (the scan body cannot pause at an inner step
        boundary).  A ``steps % k`` remainder runs via the single-step
        path on the same batch iterator.

        ``datastream`` (a train/datastream.HostShardStream, duck-typed
        on ``stream_state()``) makes every checkpoint also capture the
        data plane's position: when the checkpointer advertises
        ``accepts_stream_state`` (StateCheckpointer,
        AsyncShardedCheckpointer, FallbackCheckpointer), saves carry the
        stream state in the v3 envelope so a restored run resumes the
        record stream exactly where the lost one stopped — docs/DATA.md.
        ``batches`` should be that same stream's ``batches()`` iterator;
        the snapshot happens at the step boundary where fit saves, which
        is a batch boundary of the stream.
        """
        from deeplearning_cfn_tpu.train.data import DevicePrefetcher
        from deeplearning_cfn_tpu.train.pipeline import PipelineStats

        if steps_per_call < 1:
            raise ValueError(f"steps_per_call must be >= 1, got {steps_per_call}")
        if steps_per_call > 1:
            if reshard is not None:
                raise ValueError(
                    "steps_per_call > 1 is incompatible with live resharding: "
                    "the scanned multi-step program cannot pause at an inner "
                    "step boundary — use steps_per_call=1 for elastic runs"
                )
            return self._fit_multi(
                state,
                batches,
                steps,
                steps_per_call,
                logger=logger,
                checkpointer=checkpointer,
                stop_fn=stop_fn,
                prefetch=prefetch,
                prefetch_workers=prefetch_workers,
                profiler=profiler,
                datastream=datastream,
            )

        seams = _FitSeams(self, profiler)
        prof = seams.prof

        losses: list[float] = []
        pending: list[jax.Array] = []  # device scalars awaiting readback
        # A loss may count things beside its metrics (metrics["counters"],
        # name -> scalar: a routed layer's assignments, say); they wait for
        # the drain with the losses and are folded into obs.tracing counters
        # there, one observation a step.
        pending_counters: list[dict[str, jax.Array]] = []
        step_fn = self.step_fn
        sync_every = max(1, int(self.config.log_every))
        # islice in every mode: fit consumes exactly `steps` items from the
        # caller's iterator (a break-based guard would pull one extra).
        batches = itertools.islice(batches, steps)
        prefetcher: DevicePrefetcher | None = None
        self.last_pipeline_stats = stats = PipelineStats(name="fit")
        if prefetch > 0:
            batches = prefetcher = DevicePrefetcher(
                batches,
                self.batch_sharding,
                prefetch,
                workers=prefetch_workers,
                stats=stats,
                profiler=profiler,
            )
        # fit.data_wait = host blocked pulling the next batch (after the
        # prefetcher, so a full buffer reads as ~zero wait).
        batches = seams.source(batches)
        # Global step tracked host-side (syncing state.step every iteration
        # would stall the dispatch pipeline); resume-aware so checkpoints
        # after a restore are labeled with the true training step.
        gstep = int(jax.device_get(state.step))
        prof.start()
        try:
            for i, batch in enumerate(batches):
                with seams.step(gstep + 1):
                    if reshard is not None and reshard.pending():
                        # Pause at the step boundary: settle the losses already
                        # dispatched against the old mesh, then migrate.  The
                        # batch just pulled is trained on the NEW mesh below —
                        # the data stream continues unbroken.
                        with seams.drain(gstep, len(pending)):
                            losses.extend(float(v) for v in jax.device_get(pending))
                        pending.clear()
                        state, action = reshard.execute(self, state, step=gstep)
                        if action == "stop":
                            break
                        step_fn = self.step_fn
                    # Targets may be a pytree (e.g. detection {boxes, classes});
                    # every leaf leads with the batch axis, so one batch sharding
                    # applies uniformly.  device_put_tree skips leaves the
                    # prefetcher already placed with an equivalent sharding —
                    # prefetched batches transfer zero bytes here.
                    with seams("fit.h2d"):
                        x = device_put_tree(batch.x, self.batch_sharding)
                        y = device_put_tree(batch.y, self.batch_sharding)
                    # HOST time: the call returns when the program is enqueued,
                    # not when the device has run it (docs/OBSERVABILITY.md) — a
                    # sudden jump here means the dispatch queue filled and the
                    # host blocked.
                    with seams("fit.dispatch"):
                        with jax.set_mesh(self.mesh):
                            state, metrics = step_fn(state, x, y)
                    seams.dispatched()
                    gstep += 1
                    pending.append(metrics["loss"])
                    if "counters" in metrics:
                        pending_counters.append(metrics["counters"])
                    if i == 0:
                        self.batch_bytes_by_device = bytes_by_device((x, y))
                        seams.first_step(metrics["loss"])
                    with seams("fit.log"):
                        if logger:
                            # The logger converts to float only at its own
                            # log_every boundaries — passing the device scalar
                            # keeps non-log steps sync-free.
                            logger.step(gstep, metrics["loss"])
                        save = checkpointer is not None and checkpointer.should_save(gstep)
                    if save:
                        with seams.checkpoint(gstep):
                            self._save_checkpoint(checkpointer, gstep, state, datastream)
                    if gstep % sync_every == 0 or i == steps - 1:
                        # The host blocks here anyway, so drain the pending device
                        # scalars — O(log_every) live buffers instead of O(steps).
                        # This is where device time surfaces on the host: the
                        # blocked seconds are a lower bound on compute, which the
                        # profiler spreads over the steps drained.
                        with seams.drain(gstep, len(pending)):
                            losses.extend(float(v) for v in jax.device_get(pending))
                        pending.clear()
                        if pending_counters:
                            with seams("fit.log"):
                                _fold_counters(pending_counters)
                        if stop_fn is not None:
                            with seams("fit.log"):
                                stop = stop_fn(metrics)
                            if stop:
                                break
                    prof.step_done(step=gstep)
        finally:
            # Exceptions mid-loop must not leak a live producer thread.
            if prefetcher is not None:
                prefetcher.close()
        losses.extend(float(v) for v in jax.device_get(pending))
        _fold_counters(pending_counters)
        return state, losses

    def _fit_multi(
        self,
        state: TrainState,
        batches,
        steps: int,
        k: int,
        logger: ThroughputLogger | None = None,
        checkpointer: Any = None,
        stop_fn: Callable[[dict], bool] | None = None,
        prefetch: int = 2,
        prefetch_workers: int = 1,
        profiler: Any = None,
        datastream: Any = None,
    ) -> tuple[TrainState, list[float]]:
        """The ``steps_per_call=k`` loop: stacked, pre-staged, donated.

        Per outer iteration ONE ``multi_step_fn(k)`` dispatch consumes a
        ``[k, B, ...]`` batch stack the prefetcher already put on device
        (H2D overlapped with the previous call's compute), and the
        consumed stack is freed immediately after dispatch — deletion is
        safe in-flight, and it keeps at most ``prefetch`` stacks of HBM
        live instead of letting dead inputs pile up behind the dispatch
        queue.  Stop/checkpoint/log granularity is the k-step call.
        """
        from deeplearning_cfn_tpu.train.data import (
            DevicePrefetcher,
            donate_buffers,
            stack_batches,
        )
        from deeplearning_cfn_tpu.train.pipeline import PipelineStats

        seams = _FitSeams(self, profiler)
        prof = seams.prof
        kfn = self.multi_step_fn(k)  # built ONCE; call-many below
        stacked_sharding = NamedSharding(
            self.mesh, P(None, *self.batch_sharding.spec)
        )
        losses: list[float] = []
        pending: list[jax.Array] = []  # device [k] loss vectors
        sync_every = max(1, -(-int(self.config.log_every) // k))  # in calls
        stopped = False
        batches = itertools.islice(batches, steps)
        calls = steps // k
        stacked = stack_batches(itertools.islice(batches, calls * k), k)
        prefetcher: DevicePrefetcher | None = None
        self.last_pipeline_stats = stats = PipelineStats(name="fit")
        if prefetch > 0:
            stacked = prefetcher = DevicePrefetcher(
                stacked,
                stacked_sharding,
                prefetch,
                workers=prefetch_workers,
                stats=stats,
                profiler=profiler,
            )
        stacked = seams.source(stacked)
        gstep = int(jax.device_get(state.step))
        prof.start()
        try:
            for i, stack in enumerate(stacked):
                with seams.step(gstep + k):
                    with seams("fit.h2d"):
                        # Prefetched stacks are already resident with the
                        # stacked sharding — this is an identity check.
                        xs = device_put_tree(stack.x, stacked_sharding)
                        ys = device_put_tree(stack.y, stacked_sharding)
                        if i == 0:
                            self.batch_bytes_by_device = bytes_by_device((xs, ys))
                    with seams("fit.dispatch"):
                        with jax.set_mesh(self.mesh):
                            state, kloss = kfn(state, xs, ys)
                        # The stack was built host-side by stack_batches and
                        # placed by this loop/prefetcher, so it is ours to
                        # free.  XLA can't donate it (no same-shaped output to
                        # alias into), hence the explicit delete — see
                        # train/data.donate_buffers.
                        donate_buffers((xs, ys))
                    seams.dispatched()
                    gstep += k
                    pending.append(kloss)
                    seams.first_step(kloss)
                    with seams("fit.log"):
                        if logger:
                            logger.step(gstep, kloss[-1])
                        save = checkpointer is not None and checkpointer.should_save(gstep)
                    if save:
                        with seams.checkpoint(gstep):
                            self._save_checkpoint(checkpointer, gstep, state, datastream)
                    if (i + 1) % sync_every == 0 or i == calls - 1:
                        with seams.drain(gstep, len(pending) * k):
                            for vec in jax.device_get(pending):
                                losses.extend(float(v) for v in vec)
                        pending.clear()
                        if stop_fn is not None:
                            with seams("fit.log"):
                                stopped = bool(stop_fn({"loss": losses[-1]}))
                            if stopped:
                                break
                    prof.step_done(step=gstep, steps=k)
        finally:
            if prefetcher is not None:
                prefetcher.close()
        for vec in jax.device_get(pending):
            losses.extend(float(v) for v in vec)
        pending = []
        # Ragged tail (steps % k): the remaining batches run through the
        # ordinary single-step program — same raw step body, so the loss
        # sequence is seamless.
        if not stopped and steps % k:
            step_fn = self.step_fn
            scalar_pending: list[jax.Array] = []
            for batch in seams.source(batches):
                with seams.step(gstep + 1):
                    with seams("fit.h2d"):
                        x = device_put_tree(batch.x, self.batch_sharding)
                        y = device_put_tree(batch.y, self.batch_sharding)
                    with seams("fit.dispatch"):
                        with jax.set_mesh(self.mesh):
                            state, metrics = step_fn(state, x, y)
                    seams.dispatched()
                    gstep += 1
                    scalar_pending.append(metrics["loss"])
                    if logger:
                        with seams("fit.log"):
                            logger.step(gstep, metrics["loss"])
                    prof.step_done(step=gstep)
            with seams.drain(gstep, len(scalar_pending)):
                losses.extend(float(v) for v in jax.device_get(scalar_pending))
        return state, losses

    # --- compile diagnostics ---------------------------------------------
    @span("trainer.compile_stats")
    def compile_stats(
        self,
        state: TrainState,
        x: jax.Array,
        y: jax.Array,
        return_compiled: bool = False,
    ) -> dict | tuple[dict, Any]:
        """AOT-compile the train step and report cost analysis.  NOTE:
        ``flops_per_step`` is PER-DEVICE for an SPMD-partitioned module
        (each device executes the partitioned program over its batch
        shard) — pair it with the per-chip peak for MFU.  The compile is
        shared with the first dispatch, so it is not paid twice —
        PROVIDED the program is keyed the way fit() keys it: under
        ``jax.set_mesh(self.mesh)`` (train_step/fit do; a bare
        ``step_fn(state, x, y)`` call after this misses the entry and
        recompiles — scripts/compile_audit.py catches it), and with the
        batch described by ``self.batch_sharding``.  ``x``/``y`` are
        therefore read for shape and dtype only: a sample that sits on
        the default device, lowered as it is, keys a second program and
        the whole step compiles twice (seen on the chip, CHANGES.md
        PR 21).

        When the model supplies ``analytic_flops_fn``, ``flops_per_step``
        is the analytic estimate (divided down to per-device scope) and
        ``flops_source`` says so — XLA cost analysis excludes Pallas
        custom-call FLOPs, so on flash-attention paths the raw cost
        figure (still reported as ``cost_flops_per_step``) under-counts.

        ``return_compiled=True`` also returns the AOT executable as
        ``(stats, compiled)`` so callers (bench.py's comms block, the
        comms-audit sentinel) can read its HLO/memory analysis without
        lowering a second time — a second ``lower().compile()`` would
        count as a retrace in the compile watcher."""
        t0 = time.perf_counter()
        # Same mesh context as train_step: without it, in-model sharding
        # hints are dropped and this would measure (and compile) a different
        # program than the one that runs.

        def as_fed(tree):
            return jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(
                    a.shape, a.dtype, sharding=self.batch_sharding
                ),
                tree,
            )

        with jax.set_mesh(self.mesh):
            lowered = self.step_fn.lower(state, as_fed(x), as_fed(y))
            compiled = lowered.compile()
        cost = compiled.cost_analysis() or {}
        out = {
            "compile_seconds": time.perf_counter() - t0,
            "cost_flops_per_step": cost.get("flops"),
            "bytes_accessed": cost.get("bytes accessed"),
        }
        if self.analytic_flops_fn is not None:
            out["flops_per_step"] = self.analytic_flops_fn(x) / self.mesh.size
            out["flops_source"] = "analytic"
        else:
            out["flops_per_step"] = cost.get("flops")
            out["flops_source"] = "cost_analysis"
        if return_compiled:
            return out, compiled
        return out

    def throughput_logger(
        self,
        sample_x: jax.Array,
        examples_per_step: int,
        *,
        name: str = "train",
        sink: Any = None,
        log_every: int | None = None,
        state: TrainState | None = None,
        sample_y: jax.Array | None = None,
    ) -> "ThroughputLogger":
        """An MFU-correct ThroughputLogger for this trainer — the ONE place
        the flops-numerator choice lives, so every consumer (examples,
        ``dlcfn status`` via the metrics sink, bench harnesses) reports the
        same MFU for the same run.  Prefers the model's analytic flops
        (required for flash-attention paths); falls back to compiled cost
        analysis when ``state``/``sample_y`` are given; otherwise logs
        throughput without MFU.  Scope is per-chip on both sides:
        per-device flops over per-chip peak."""
        peak = peak_flops_per_chip()
        flops = None
        if peak is not None:
            if self.analytic_flops_fn is not None:
                fx = sample_x
                if self.config.augment is not None:
                    # Analytic flops follow the MODEL's input shape: the
                    # augment stage may crop stored-size samples down.
                    fx = self.config.augment(
                        jnp.zeros((), jnp.int32), jnp.asarray(sample_x)
                    )
                flops = self.analytic_flops_fn(fx) / self.mesh.size
            elif state is not None and sample_y is not None:
                flops = self.compile_stats(state, sample_x, sample_y)[
                    "flops_per_step"
                ]
        return ThroughputLogger(
            global_batch_size=examples_per_step,
            log_every=log_every if log_every is not None else self.config.log_every,
            name=name,
            sink=sink,
            flops_per_step=flops,
            peak_flops=peak,
        )


@dataclass
class EpochPlan:
    """STEPS_PER_EPOCH = numerator / total_chips — the linear-scaling
    contract from run.sh:56,66, made explicit."""

    examples_per_epoch: int
    global_batch_size: int
    epochs: int = 1
    history: list[dict] = field(default_factory=list)

    @property
    def steps_per_epoch(self) -> int:
        return max(1, self.examples_per_epoch // self.global_batch_size)

    @property
    def total_steps(self) -> int:
        return self.steps_per_epoch * self.epochs
