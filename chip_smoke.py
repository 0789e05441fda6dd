"""The quickest proof that the system still starts on the chip.

``python chip_smoke.py`` drives the main path once, in one process, through
the entry points an operator uses, on whatever TPU host it is started on
(one v5e chip, or one host with four), and exits 0 only if every phase
passed:

1. *device* — JAX's platform must be ``tpu`` and the device kind one the
   MFU table knows.  Anything else (this repo's CPU sandbox, a TPU machine
   whose chip failed to initialise and fell back to the CPU) is refused
   before anything compiles: non-zero exit, no result line.
2. *trainer* — ``dlcfn run templates/chip-smoke.json``: template → local
   backend provision → discovery contract → launch plan →
   ``examples.resnet_imagenet`` (ResNet-50, 224 px, bf16, 128 images per
   chip, data-parallel over every chip) → ``Trainer.fit``.
3. *llama* — ``examples.llama_train --size 435m --seq_len 2048``, the one
   training path that runs a Pallas kernel (flash attention); on four
   chips as fsdp=2 x tp=2.  The lowered step must contain a Mosaic call.
4. *kernels* — ``flash_attention`` forward and gradients against
   ``ops.attention.dot_product_attention``, ``fused_dense`` and
   ``fused_dense_quantized`` against their references, compiled
   (``interpret=False``), within the tolerances below.
5. *serving* — ``ContinuousBatchingEngine`` at ``LlamaConfig.m435`` in bf16
   on the wall clock: a handful of requests of mixed prompt lengths, every
   generated token checked against the plain forward pass and the first
   against ``llama_decode.generate``.

A failed check raises: there is no handler that prints and carries on.  The
phases are plain functions of their sizes; tests/test_chip_smoke.py drives
each at toy size on the CPU mesh.  Details of a run land in
``chiprun_out/chip_smoke/runs.jsonl``.  Stdout is two JSON lines: the run
(``{"phases": {name: seconds}, "wall_s": ..., "compile_cache": {...}}`` — a
second invocation against the same cache directory shows ``hits`` where
the first showed ``misses``), then, last, the result and nothing else:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Weights are random from a seed and depth is whatever the model has: nothing
here is a benchmark and no number it prints is a performance claim.
"""

from __future__ import annotations

import contextlib
import io
import json
import math
import os
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
OUT_DIR = REPO / "chiprun_out" / "chip_smoke"
TEMPLATE = REPO / "templates" / "chip-smoke.json"

# --- tolerances ------------------------------------------------------------
# Error of a kernel against its XLA reference on the same inputs, as
# max|got - want| / max(1, max|want|).  Each bound is about four times the
# largest value measured on a TPU v5 lite with jax 0.9.0 / libtpu 0.0.34
# (PR 21), which is the number in the comment.  Bit-identity is not
# expected: the MXU and XLA's fusions each sum in their own order.
TOLERANCE = {
    ("flash_fwd", "bfloat16"): 2.5e-2,  # 5.9e-3
    ("flash_bwd", "bfloat16"): 3.5e-2,  # 8.4e-3 (dq, 32/8 heads at S=2048)
    ("flash_fwd", "float32"): 1e-2,  # 2.5e-3: f32 matmuls run as bf16 passes
    ("flash_bwd", "float32"): 2e-2,  # 4.3e-3
    ("fused_dense", "bfloat16"): 1e-2,  # 2.6e-3 (K=4096)
    ("fused_dense", "float32"): 1e-5,  # 1.6e-7
    ("fused_dense_quantized", "bfloat16"): 1e-2,  # 2.6e-3
    ("fused_dense_quantized", "float32"): 1e-5,  # 1.6e-7
}
# Serving: how far below the reference's best logit a generated token's
# logit may sit.  Random weights give ~N(0, 1) logits over a 32k vocabulary
# and bf16 rounds them to 1/32 near the top, so the leaders tie often and
# paths that sum in different orders may each pick another of them.
# Measured worst gap 0.021 (one bf16 step at that magnitude is 0.031).
SERVE_LOGIT_TOLERANCE = 0.1

# (batch, seq, q heads, kv heads, head dim, dtype): the shapes the repo's
# configurations produce (m435 8x8x128, a GQA 16/4 at d64, an 8B-like 32/8),
# each at the flash crossover and above it, two ragged lengths (600 pads to
# 640, 2100 to 2176: whole 128-blocks of which the last is mostly padding,
# where the compiled backward's padded rows would make a NaN), one f32.
ATTENTION_CASES = (
    (1, 2048, 8, 8, 128, "bfloat16"),
    (1, 4096, 8, 8, 128, "bfloat16"),
    (1, 2048, 16, 4, 64, "bfloat16"),
    (1, 4096, 16, 4, 64, "bfloat16"),
    (1, 2048, 32, 8, 128, "bfloat16"),
    (1, 4096, 32, 8, 128, "bfloat16"),
    (2, 600, 8, 8, 128, "bfloat16"),
    (1, 2100, 32, 8, 128, "bfloat16"),
    (1, 2048, 8, 8, 128, "float32"),
)
# (M, K, N, activation, dtype): BERT-base MLP in and out, the ResNet head,
# and an 8B-like MLP projection.
DENSE_CASES = (
    (4096, 768, 3072, "gelu", "bfloat16"),
    (4096, 3072, 768, None, "bfloat16"),
    (128, 2048, 1000, None, "float32"),
    (8192, 4096, 14336, None, "bfloat16"),
)


class SmokeFailure(AssertionError):
    """A phase's check did not hold."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


# --- device ------------------------------------------------------------------


def require_tpu() -> dict:
    """Phase 1: the device JAX found, or a refusal.  Sets no platform and
    overrides none — what JAX picks by itself is the thing under test."""
    import jax

    from deeplearning_cfn_tpu.train.metrics import PEAK_BF16_FLOPS_PER_CHIP

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(
        f"chip_smoke: jax {jax.__version__}, platform {device['platform']}, "
        f"device_kind {device['kind']!r}, {device['count']} device(s)",
        file=sys.stderr,
    )
    if device["platform"] != "tpu" or device["kind"] not in PEAK_BF16_FLOPS_PER_CHIP:
        raise SystemExit(
            "chip_smoke: refusing to run: needs platform 'tpu' with a device "
            f"kind in PEAK_BF16_FLOPS_PER_CHIP, found {device}. Run it "
            "through the chip tool; on the CPU use the test suite."
        )
    return device


class CacheCounter:
    """Counts JAX's own persistent-cache events for this process."""

    def __init__(self) -> None:
        import jax

        self.requests = self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        # misses = programs compiled here and written for the next run.
        return {"requests": self.requests, "hits": self.hits, "misses": self.misses}


def device_memory() -> dict:
    """Per device id, what the allocator reports (None off a chip)."""
    import jax

    out = {}
    for d in jax.devices():
        stats = d.memory_stats() or {}
        out[d.id] = {
            "bytes_in_use": stats.get("bytes_in_use"),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }
    return out


# --- what every training run must show --------------------------------------


def check_training_run(result: dict, *, steps: int, expect_mfu: bool) -> dict:
    """The checks shared by the trainer and llama phases, on the dict an
    example's ``main`` returns (``examples/common.run_report``)."""
    import jax

    losses = result["losses"]
    check(len(losses) == steps, f"ran {len(losses)} steps, wanted {steps}")
    check(all(math.isfinite(v) for v in losses), f"non-finite loss in {losses}")
    check(result["step"] == steps, f"step counter at {result['step']}, wanted {steps}")
    check(result["params_changed"], "the parameters did not move")
    first = result["first_step_s"]
    check(
        isinstance(first, float) and math.isfinite(first) and first > 0,
        f"first_step_s is {first!r}",
    )
    if expect_mfu:
        history = result["history"]
        check(bool(history), "the throughput logger recorded nothing")
        for record in history:
            mfu = record.get("mfu")
            check(
                isinstance(mfu, float) and math.isfinite(mfu) and mfu > 0,
                f"logger MFU is {mfu!r} at step {record.get('step')}",
            )
    ids = sorted(d.id for d in jax.devices())
    for name in ("state_bytes_by_device", "batch_bytes_by_device"):
        # Keys are ints from an example, strings once through dlcfn run's JSON.
        placed = {int(k): v for k, v in result[name].items()}
        check(
            sorted(placed) == ids and all(v > 0 for v in placed.values()),
            f"{name} {placed} does not cover devices {ids}",
        )
    batch = result["batch_bytes_by_device"]
    check(
        len(set(batch.values())) == 1,
        f"the input batch is not split evenly over the devices: {batch}",
    )
    return {
        "steps": steps,
        "first_loss": losses[0],
        "final_loss": losses[-1],
        "first_step_s": round(first, 2),
        "mfu": [round(r["mfu"], 4) for r in result["history"] if "mfu" in r],
        "examples_per_sec": [
            round(r["examples_per_sec"], 1) for r in result["history"]
        ],
        "state_bytes_by_device": result["state_bytes_by_device"],
        "batch_bytes_by_device": result["batch_bytes_by_device"],
    }


def check_memory_balance(memory: dict, report: dict) -> None:
    """Several chips: no pile-up on device 0 after the training phases."""
    peaks = [m["peak_bytes_in_use"] for m in memory.values()]
    report["memory"] = memory
    if len(peaks) < 2 or any(p is None for p in peaks):
        return
    check(
        max(peaks) <= 1.25 * min(peaks),
        f"peak device memory is lopsided: {memory}",
    )


# --- phase 2: template -> cluster -> ResNet-50 trainer ------------------------


def trainer_phase(
    root: Path,
    *,
    batch_per_chip: int = 128,
    image_size: int = 224,
    steps: int = 30,
    expect_mfu: bool = True,
) -> dict:
    """``dlcfn run`` on the committed local-backend template, the job
    executing in this process (one process per chip host by construction:
    cli.cmd_run -> launcher.LocalJobRunner)."""
    import jax

    from deeplearning_cfn_tpu import cli
    from deeplearning_cfn_tpu.cluster.contract import ClusterContract

    chips = len(jax.devices())
    argv = [
        "run", str(TEMPLATE),
        "-P", f"Workers={chips}",
        "-P", f"Batch={batch_per_chip * chips}",
        "-P", f"Steps={steps}",
        "-P", f"ImageSize={image_size}",
    ]
    # The contract is published under $DLCFN_ROOT (default /opt/deeplearning);
    # it stays pointed at ``root`` for the rest of the process.
    os.environ["DLCFN_ROOT"] = str(root)
    captured = io.StringIO()
    with contextlib.redirect_stdout(captured):
        rc = cli.main(argv)
    check(rc == 0, f"dlcfn run exited {rc}: {captured.getvalue()[-2000:]}")
    record = json.loads(captured.getvalue().strip().splitlines()[-1])
    contract = ClusterContract.read(root)
    check(
        contract.total_chips == chips,
        f"the contract counts {contract.total_chips} chips, JAX {chips}",
    )
    t2fs = record.get("template_to_first_step_s")
    check(
        isinstance(t2fs, float) and math.isfinite(t2fs) and t2fs > 0,
        f"template_to_first_step_s is {t2fs!r}",
    )
    report = check_training_run(record["result"], steps=steps, expect_mfu=expect_mfu)
    state = record["result"]["state_bytes_by_device"]
    check(
        len(set(state.values())) == 1,
        f"data-parallel state is not the same size on every device: {state}",
    )
    report.update(
        job=record["job"],
        global_batch=batch_per_chip * chips,
        image_size=image_size,
        template_to_first_step_s=t2fs,
        contract={
            "workers": contract.workers_count,
            "chips_per_worker": contract.chips_per_worker,
            "total_chips": contract.total_chips,
        },
    )
    return report


# --- phase 3: the Llama step, where the Pallas kernel lives -----------------


def llama_layout(chips: int) -> tuple[int, int]:
    """(fsdp, tp) for the llama phase: tensor-parallel pairs where the
    host has four chips or more, fsdp over what remains."""
    tp = 2 if chips % 4 == 0 else 1
    return chips // tp, tp


def lowered_llama_step(size: str, seq_len: int, batch: int, fsdp: int, tp: int) -> str:
    """StableHLO text of the train step ``llama_train`` builds for this
    size and layout — traced from shapes alone, nothing executes."""
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.examples.llama_train import size_config
    from deeplearning_cfn_tpu.models import llama
    from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
    from deeplearning_cfn_tpu.train.trainer import TrainerConfig

    cfg = size_config(size, seq_len)
    mesh = build_mesh(MeshSpec(fsdp=fsdp, tp=tp))
    trainer = llama.make_trainer(
        cfg, mesh, TrainerConfig(strategy="fsdp", optimizer="adamw")
    )
    tokens = jax.ShapeDtypeStruct((batch, seq_len), jnp.int32)
    state = jax.eval_shape(trainer.init, jax.random.key(0), tokens)
    with jax.set_mesh(mesh):
        return trainer.step_fn.lower(state, tokens, tokens).as_text()


def llama_phase(
    *,
    size: str = "435m",
    seq_len: int = 2048,
    batch_per_chip: int = 4,
    steps: int = 6,
    expect_mosaic: bool = True,
    expect_mfu: bool = True,
) -> dict:
    import jax

    from deeplearning_cfn_tpu.examples import llama_train

    chips = len(jax.devices())
    fsdp, tp = llama_layout(chips)
    batch = batch_per_chip * chips
    result = llama_train.main([
        "--size", size,
        "--seq_len", str(seq_len),
        "--steps", str(steps),
        "--global_batch_size", str(batch),
        "--fsdp", str(fsdp),
        "--tp", str(tp),
        "--log_every", "2",
    ])
    report = check_training_run(result, steps=steps, expect_mfu=expect_mfu)
    report.update(
        size=size, seq_len=seq_len, global_batch=batch, mesh=result["mesh"],
        params=result["params"], attention=result["attention"],
    )
    if expect_mosaic:
        check(
            result["attention"] == "flash",
            f"the run used {result['attention']!r} attention, not the Pallas kernel",
        )
        text = lowered_llama_step(size, seq_len, batch, fsdp, tp)
        report["mosaic_calls_in_lowered_step"] = text.count("tpu_custom_call")
        check(
            report["mosaic_calls_in_lowered_step"] > 0,
            "no Mosaic custom call in the lowered train step",
        )
    return report


# --- phase 4: the kernels against their references ---------------------------


def _error(got, want) -> float:
    """max|got - want| / max(1, max|want|); raises on a non-finite value."""
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    check(got.shape == want.shape, f"shape {got.shape} vs reference {want.shape}")
    check(bool(jnp.isfinite(got).all()), "kernel produced a non-finite value")
    scale = max(1.0, float(jnp.max(jnp.abs(want))))
    return float(jnp.max(jnp.abs(got - want))) / scale


def attention_case(case, interpret: bool) -> dict:
    """Flash attention forward and (dq, dk, dv) for one cotangent against
    dot_product_attention.  The reference runs one kv head at a time so
    that its [S, S] scores never hold more than one GQA group."""
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ops.attention import dot_product_attention
    from deeplearning_cfn_tpu.ops.pallas_attention import flash_attention

    B, S, Hq, Hkv, D, dtype_name = case
    dtype = jnp.dtype(dtype_name)
    group = Hq // Hkv
    kq, kk, kv, kg = jax.random.split(jax.random.key(S + Hq), 4)
    q = jax.random.normal(kq, (B, S, Hq, D), dtype)
    k = jax.random.normal(kk, (B, S, Hkv, D), dtype)
    v = jax.random.normal(kv, (B, S, Hkv, D), dtype)
    g = jax.random.normal(kg, (B, S, Hq, D), dtype)

    @jax.jit
    def flash(q, k, v, g):
        out, vjp = jax.vjp(
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=interpret),
            q, k, v,
        )
        return (out, *vjp(g))

    @jax.jit
    def reference(q, k, v, g):
        def by_kv_head(x, heads):  # [B, S, Hkv * heads, D] -> [Hkv, B, S, heads, D]
            return jnp.moveaxis(x.reshape(B, S, Hkv, heads, D), 2, 0)

        def one_group(args):
            qh, kh, vh, gh = args
            out, vjp = jax.vjp(
                lambda q, k, v: dot_product_attention(q, k, v, causal=True),
                qh, kh, vh,
            )
            return (out, *vjp(gh))

        out, dq, dk, dv = jax.lax.map(
            one_group,
            (by_kv_head(q, group), by_kv_head(k, 1), by_kv_head(v, 1), by_kv_head(g, group)),
        )
        back = lambda x, heads: jnp.moveaxis(x, 0, 2).reshape(B, S, Hkv * heads, D)
        return back(out, group), back(dq, group), back(dk, 1), back(dv, 1)

    got, want = flash(q, k, v, g), reference(q, k, v, g)
    errors = {
        name: _error(a, b) for name, a, b in zip(("out", "dq", "dk", "dv"), got, want)
    }
    fwd, bwd = errors["out"], max(errors["dq"], errors["dk"], errors["dv"])
    label = f"flash_attention B{B} S{S} Hq{Hq} Hkv{Hkv} D{D} {dtype_name}"
    check(fwd <= TOLERANCE["flash_fwd", dtype_name], f"{label}: forward off by {fwd:.3g}")
    check(bwd <= TOLERANCE["flash_bwd", dtype_name], f"{label}: gradient off by {bwd:.3g}")
    return {"case": label, **{k: float(f"{e:.3g}") for k, e in errors.items()}}


def dense_case(case, interpret: bool) -> dict:
    import jax
    import jax.numpy as jnp

    from deeplearning_cfn_tpu.ops.pallas_fused import (
        _quant_reference,
        fused_dense,
        fused_dense_quantized,
        fused_dense_reference,
    )
    from deeplearning_cfn_tpu.ops.quant import quantize_weight

    M, K, N, activation, dtype_name = case
    dtype = jnp.dtype(dtype_name)
    kx, kw, kb = jax.random.split(jax.random.key(M + N), 3)
    x = jax.random.normal(kx, (M, K), dtype)
    w = (jax.random.normal(kw, (K, N), jnp.float32) / math.sqrt(K)).astype(dtype)
    b = (jax.random.normal(kb, (N,), jnp.float32) * 0.1).astype(dtype)
    wq, scale = jax.jit(quantize_weight)(w)

    got = fused_dense(x, w, b, activation=activation, interpret=interpret)
    want = jax.jit(
        lambda x, w, b: fused_dense_reference(x, w, b, activation=activation)
    )(x, w, b)
    got_q = fused_dense_quantized(
        x, wq, scale, b, activation=activation, interpret=interpret
    )
    want_q = jax.jit(
        lambda x, wq, s, b: _quant_reference(x, wq, s, b, activation, x.dtype)
    )(x, wq, scale, b)
    err, err_q = _error(got, want), _error(got_q, want_q)
    label = f"{M}x{K}x{N} {activation} {dtype_name}"
    check(
        err <= TOLERANCE["fused_dense", dtype_name],
        f"fused_dense {label}: off by {err:.3g}",
    )
    check(
        err_q <= TOLERANCE["fused_dense_quantized", dtype_name],
        f"fused_dense_quantized {label}: off by {err_q:.3g}",
    )
    return {
        "case": label,
        "fused_dense": float(f"{err:.3g}"),
        "fused_dense_quantized": float(f"{err_q:.3g}"),
    }


def kernel_phase(
    *,
    attention_cases=ATTENTION_CASES,
    dense_cases=DENSE_CASES,
    interpret: bool = False,
) -> dict:
    """``interpret`` is passed through by name: False on the chip (the
    compiled Mosaic kernels), True only from the CPU test."""
    return {
        "interpret": interpret,
        "attention": [attention_case(c, interpret) for c in attention_cases],
        "dense": [dense_case(c, interpret) for c in dense_cases],
    }


# --- phase 5: the serving engine, once ------------------------------------------


def serving_phase(
    *,
    cfg=None,
    num_slots: int = 4,
    block_size: int = 16,
    blocks_per_slot: int = 8,
    prefill_len: int = 64,
    requests=((5, 8), (17, 4), (33, 8), (64, 6), (17, 8)),
    tolerance: float = SERVE_LOGIT_TOLERANCE,
) -> dict:
    """ContinuousBatchingEngine on the wall clock at the model's full
    width.  ``requests`` are (prompt length, new tokens): more of them
    than slots and of unequal lengths, so admission into a running batch
    and page recycling both happen.  Every generated token must be, by the
    plain forward pass over the same tokens, within ``tolerance`` of the
    best logit at its position; the first must also agree with
    ``llama_decode.generate`` or tie with its choice."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning_cfn_tpu.models import llama
    from deeplearning_cfn_tpu.models.llama_decode import generate
    from deeplearning_cfn_tpu.parallel.sharding import bytes_by_device
    from deeplearning_cfn_tpu.serve import (
        ContinuousBatchingEngine,
        ServeConfig,
        ServeRequest,
        plan_placement,
    )

    if cfg is None:
        cfg = llama.LlamaConfig.m435(seq_len=block_size * blocks_per_slot)
    params = jax.jit(lambda key: llama.init_params(cfg, key))(jax.random.key(0))
    engine = ContinuousBatchingEngine(
        cfg,
        params,
        ServeConfig(
            num_slots=num_slots,
            block_size=block_size,
            blocks_per_slot=blocks_per_slot,
            prefill_len=prefill_len,
        ),
        journal=False,
    )
    rng = np.random.default_rng(0)
    prompts = {
        f"r{i}": rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
        for i, (n, _) in enumerate(requests)
    }
    wanted = {f"r{i}": new for i, (_, new) in enumerate(requests)}
    t0 = time.monotonic()
    for rid, prompt in prompts.items():
        engine.submit(ServeRequest(rid, prompt, wanted[rid]))
    done = {}
    while engine.pending():
        check(engine.steps < 100 * len(prompts), "the engine is not draining")
        for completion in engine.step():
            done[completion.request_id] = completion
    wall = time.monotonic() - t0
    check(sorted(done) == sorted(prompts), f"completed {sorted(done)}")
    for c in done.values():
        check(
            len(c.tokens) == wanted[c.request_id]
            and all(0 <= t < cfg.vocab_size for t in c.tokens),
            f"{c.request_id}: bad tokens {c.tokens}",
        )

    # Teacher-forced reference: prompt + the engine's own tokens through the
    # plain forward pass, all requests padded into one batch (causal, so the
    # padding after a sequence cannot reach the positions read).
    width = max(n + new - 1 for n, new in requests)
    ids = list(prompts)
    batch = np.zeros((len(ids), width), np.int32)
    for row, rid in enumerate(ids):
        seq = np.concatenate([prompts[rid], np.asarray(done[rid].tokens[:-1], np.int32)])
        batch[row, : seq.size] = seq
    logits = np.asarray(
        jax.jit(lambda p, t: llama.forward(cfg, p, t))(params, jnp.asarray(batch))
    )
    check(bool(np.isfinite(logits).all()), "reference logits are not finite")
    worst_gap, first_matches, per_request = 0.0, 0, []
    for row, rid in enumerate(ids):
        start = prompts[rid].size - 1
        rows = logits[row, start : start + wanted[rid]]
        chosen = rows[np.arange(wanted[rid]), done[rid].tokens]
        gaps = rows.max(axis=-1) - chosen
        worst_gap = max(worst_gap, float(gaps.max()))
        check(
            float(gaps.max()) <= tolerance,
            f"{rid}: token {int(gaps.argmax())} sits {gaps.max():.3f} below the "
            f"reference's best logit (tolerance {tolerance})",
        )
        ref_first = int(
            np.asarray(
                generate(cfg, params, jnp.asarray(prompts[rid][None]), jax.random.key(0),
                         max_new_tokens=1)
            )[0, 0]
        )
        same = ref_first == done[rid].tokens[0]
        first_matches += same
        check(
            same or abs(float(rows[0, ref_first] - chosen[0])) <= tolerance,
            f"{rid}: first token {done[rid].tokens[0]} vs generate's {ref_first}, "
            "and the reference logits do not tie them",
        )
        per_request.append(
            {"id": rid, "prompt_len": int(prompts[rid].size),
             "max_gap": float(f"{gaps.max():.3g}"), "first_token_matches_generate": bool(same)}
        )
    snapshot = engine.snapshot()
    plan = plan_placement()
    return {
        "model": {"dim": cfg.dim, "layers": cfg.n_layers, "heads": cfg.n_heads,
                  "vocab": cfg.vocab_size, "dtype": str(jnp.dtype(cfg.dtype))},
        "requests": [list(r) for r in requests],
        "engine_steps": snapshot["steps"],
        "prefills": engine.prefills,
        "recycled_blocks": snapshot["recycled_blocks"],
        "wall_s": round(wall, 2),
        "ttft_ms": snapshot["ttft_ms"],
        "itl_ms": snapshot["itl_ms"],
        "worst_logit_gap": float(f"{worst_gap:.3g}"),
        "first_tokens_matching_generate": f"{first_matches}/{len(ids)}",
        "per_request": per_request,
        # Where things live.  With placement=None (what this phase and
        # `dlcfn serve` without --disaggregate use) everything goes to the
        # default device; plan_placement() would hand out decode_devices[1:]
        # of which the engine uses only [0].  Reported, not redesigned here.
        "placement": None,
        "params_bytes_by_device": bytes_by_device(engine.params),
        "cache_bytes_by_device": bytes_by_device(engine.cache),
        "plan_placement_would_give": plan.describe(),
    }


# --- the run ------------------------------------------------------------------


def main() -> int:
    t_start = time.monotonic()
    device = require_tpu()

    import jax
    import jaxlib

    from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    cache = CacheCounter()
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    phases: dict[str, float] = {}
    detail: dict = {
        "device": device,
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": _version_of("libtpu"),
    }

    def run(name: str, fn, **kwargs) -> dict:
        t0 = time.monotonic()
        print(f"chip_smoke: {name} ...", file=sys.stderr)
        report = fn(**kwargs)
        phases[name] = round(time.monotonic() - t0, 1)
        report["cache_after"] = cache.snapshot()
        detail[name] = report
        print(f"chip_smoke: {name} ok in {phases[name]} s", file=sys.stderr)
        return report

    run("trainer", trainer_phase, root=OUT_DIR / "dlcfn_root")
    check_memory_balance(device_memory(), detail["trainer"])
    run("llama", llama_phase)
    check_memory_balance(device_memory(), detail["llama"])
    run("kernels", kernel_phase)
    run("serving", serving_phase)
    detail["serving"]["memory"] = device_memory()

    detail["summary"] = {
        "phases": phases,
        "wall_s": round(time.monotonic() - t_start, 1),
        "compile_cache": {"dir": cache_dir, **cache.snapshot()},
    }
    # One line per invocation: a cold run and the warm one after it sit
    # side by side.
    with open(OUT_DIR / "runs.jsonl", "a") as f:
        f.write(json.dumps(detail, default=str) + "\n")
    print(json.dumps(detail["summary"]))
    print(json.dumps({"ok": True, "device": device}))
    return 0


def _version_of(dist: str) -> str | None:
    from importlib import metadata

    try:
        return metadata.version(dist)
    except metadata.PackageNotFoundError:
        return None


if __name__ == "__main__":
    sys.exit(main())
