"""One-shot on-chip measurement: python chip_measure.py <mode> [args]

Modes:
  throughput <size> <batch> <seq> [adafactor]        — warmup+timed train steps
  fit <size> <batch> <seq> [adafactor]               — init + 2 steps; FITS/OOM
  decode <size> <batch> <prompt_len> [new_tokens]    — serving tokens/s + MBU

The optional trailing token selects the adafactor optimizer (the
memory-lean rung that admits --size 3b on the 16 GiB chip; adamw cannot
hold its moment state at that scale).

``decode`` measures the llama_decode.generate path (prefill + lax.scan
decode, KV cache, greedy): tokens/s and MBU — model-bandwidth
utilization, param-bytes-only numerator — because each decode step must
stream the weights from HBM once, bandwidth (not the MXU) is the
ceiling that matters for serving.
"""
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning_cfn_tpu.utils.compile_cache import enable_compile_cache
from deeplearning_cfn_tpu.train.metrics import (
    json_safe,
    peak_flops_per_chip,
    utilization,
)
from deeplearning_cfn_tpu.models import llama
from deeplearning_cfn_tpu.parallel.mesh import MeshSpec, build_mesh
from deeplearning_cfn_tpu.train.trainer import TrainerConfig

enable_compile_cache()

mode, size, batch, seq = sys.argv[1], sys.argv[2], int(sys.argv[3]), int(sys.argv[4])
optimizer = "adafactor" if "adafactor" in sys.argv[5:] else "adamw"

new_tokens = int(sys.argv[5]) if mode == "decode" and len(sys.argv) > 5 else 128
cfg = {"435m": llama.LlamaConfig.m435, "1b": llama.LlamaConfig.b1,
       "3b": llama.LlamaConfig.b3}[size](
    # decode: seq is the PROMPT length; the cache needs prompt + new room.
    seq_len=seq + new_tokens if mode == "decode" else seq
)

if mode == "decode":
    from deeplearning_cfn_tpu.models.llama_decode import generate
    from deeplearning_cfn_tpu.train.metrics import peak_hbm_bytes_per_chip

    batch_, prompt_len = batch, seq  # positional reuse: <batch> <prompt_len>
    params = llama.init_params(cfg, jax.random.key(0))
    param_bytes = sum(p.nbytes for p in jax.tree_util.tree_leaves(params))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(1, cfg.vocab_size, (batch_, prompt_len)), jnp.int32
    )
    assert new_tokens > 1, "decode mode needs >= 2 new tokens"
    out = generate(cfg, params, prompt, jax.random.key(1),
                   max_new_tokens=new_tokens)  # compile + warm
    np.asarray(out)
    # Prefill probe: same prompt, ONE new token.  Subtracting its time
    # isolates the decode steps — otherwise every rep charges a full
    # prefill to the per-step and MBU numbers, understating both (the
    # more the longer the prompt).
    pre = generate(cfg, params, prompt, jax.random.key(1), max_new_tokens=1)
    np.asarray(pre)
    REPS = 5
    t0 = time.perf_counter()
    for i in range(REPS):
        out = generate(cfg, params, prompt, jax.random.key(2 + i),
                       max_new_tokens=new_tokens)
    np.asarray(out)  # the readback ends the timed window
    dt_full = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(REPS):
        pre = generate(cfg, params, prompt, jax.random.key(2 + i),
                       max_new_tokens=1)
    np.asarray(pre)
    dt_pre = time.perf_counter() - t0
    # Wall-time variance can make the subtraction go negative on
    # short-prompt shapes; floor at 10% of the naive step time.
    naive = dt_full / (REPS * new_tokens)
    step_s = max((dt_full - dt_pre) / (REPS * (new_tokens - 1)), 0.1 * naive)
    toks = batch_ * new_tokens * REPS / dt_full  # end-to-end incl. prefill
    print(json.dumps(json_safe({
        "mode": "decode", "size": size, "batch": batch_,
        "prompt_len": prompt_len, "new_tokens": new_tokens,
        "param_bytes": param_bytes,
        "tokens_per_sec": round(toks, 1),
        "prefill_ms": round(1000 * dt_pre / REPS, 2),
        # Per decode STEP (= per token per stream), prefill-subtracted;
        # at B>1 each step serves B tokens, which is what
        # tokens_per_sec aggregates.
        "ms_per_step": round(1000 * step_s, 2),
        # null (not NaN) off a TPU — the JSON stays strictly parseable
        # on the CPU test backend.
        "mbu": utilization(param_bytes / step_s, peak_hbm_bytes_per_chip()),
    }), allow_nan=False))
    sys.exit(0)

mesh = build_mesh(MeshSpec.fsdp_parallel(len(jax.devices())))
trainer = llama.make_trainer(
    cfg, mesh, TrainerConfig(strategy="fsdp", optimizer=optimizer, learning_rate=1e-4)
)
rng = np.random.default_rng(0)
tok = jax.device_put(
    jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, seq)), jnp.int32),
    trainer.batch_sharding,
)
tgt = jax.device_put(jnp.roll(tok, -1, axis=1), trainer.batch_sharding)

try:
    state = trainer.init(jax.random.key(0), tok[:1])
    if mode == "fit":
        for _ in range(2):
            state, metrics = trainer.train_step(state, tok, tgt)
        loss = float(metrics["loss"])
        print(json.dumps(json_safe(
            {"mode": "fit", "size": size, "batch": batch,
             "seq": seq, "result": "FITS", "loss": round(loss, 3)}
        ), allow_nan=False))
        sys.exit(0)
    WARM, MEAS = 3, 10
    for _ in range(WARM):
        state, metrics = trainer.train_step(state, tok, tgt)
    float(metrics["loss"])  # the readback ends the warm-up
    t0 = time.perf_counter()
    for _ in range(MEAS):
        state, metrics = trainer.train_step(state, tok, tgt)
    loss = float(metrics["loss"])
    dt = time.perf_counter() - t0
    toks = batch * seq * MEAS / dt
    flops_tok = llama.train_flops_per_token(cfg, seq)
    # Device-kind dispatch, not a hardcoded v5e constant: the same
    # harness must report honest MFU on v4/v5p chips too; a TPU kind
    # missing from the table raises.
    mfu = utilization(
        flops_tok * batch * seq * MEAS / dt,
        peak_flops_per_chip(jax.devices()[0]),
    )
    print(json.dumps(json_safe({
        "mode": "throughput", "size": size, "batch": batch, "seq": seq,
        "optimizer": optimizer, "tokens_per_sec": round(toks, 1),
        "ms_per_step": round(1000 * dt / MEAS, 1), "mfu": mfu,
        "loss": round(loss, 3),
    }), allow_nan=False))
except Exception as e:
    msg = str(e)
    oom = "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg or "exceeds" in msg
    print(json.dumps({"mode": mode, "size": size, "batch": batch, "seq": seq,
                      "result": "OOM" if oom else "ERROR",
                      "detail": msg[:300]}, allow_nan=False))
    sys.exit(2)
