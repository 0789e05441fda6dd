"""FLOPs and bytes of the flash attention under a sliding window, one forward
call and one backward pass (all of its kernels together), for their rooflines:
B sequences of S tokens, H query heads sharing KV key/value heads of size hd,
in a 2-byte type, query t seeing the keys t - W < j <= t.

The work is counted over the band, not over the tiles an implementation runs:
a head computes `scores(S, W) = S W - W (W - 1) / 2` scores (every query W keys,
less the first W - 1 queries' missing ones), so a (q block, kv block) pair that
is skipped is not credited and a pair that is half masked is credited its
visible half.  Forward: QK^T and PV, 2 hd each a score.  Backward: the least
the algorithm needs, five block matmuls a score (`flops/attention_backward.py`
has the reasoning); a design that recomputes two is measured against the same
five.  Bytes: what `flops/attention.py` and `flops/attention_backward.py`
count, which a window does not shrink: every q, k, v row is still read once and
every result row written once."""

from __future__ import annotations

BACKWARD_BLOCK_MATMULS = 5


def scores(s: int, window: int) -> float:
    """Scores one query head computes over a sequence of s under the window."""
    w = min(int(window), int(s))
    return float(s * w - w * (w - 1) / 2)


def flops(b: int, s: int, h: int, hd: int, window: int) -> float:
    return 2.0 * 2 * hd * b * h * scores(s, window)


def bytes_moved(b: int, s: int, h: int, kv: int, hd: int, itemsize: int = 2) -> float:
    return float(b * s * hd * (2 * h + 2 * kv) * itemsize + b * h * s * 4)


def backward_flops(b: int, s: int, h: int, hd: int, window: int) -> float:
    return 2.0 * BACKWARD_BLOCK_MATMULS * hd * b * h * scores(s, window)


def backward_bytes_moved(b: int, s: int, h: int, kv: int, hd: int, itemsize: int = 2) -> float:
    tensors = b * s * hd * (4 * h + 4 * kv) * itemsize  # q, out, dout, dq; k, v, dk, dv
    statistics = b * h * s * 4 * 3  # lse read, delta written and read
    return float(tensors + statistics)


def window_layers(config: dict) -> tuple[int, int] | None:
    """(how many layers attend under the window, their query heads), or None
    where the configuration has no such layer or they differ in head count (a
    call's cost is then not one number)."""
    heads = [
        int(h) for kind, h in zip(
            config.get("layer_types", ()), config.get("num_attention_heads_per_layer", ())
        ) if kind == "sliding_attention"
    ]
    if not heads or len(set(heads)) != 1 or "sliding_window" not in config:
        return None
    return len(heads), heads[0]


def call_shape(run: dict) -> dict | None:
    """One windowed call's shape in a run of the benchmark, as this file's
    functions take it, or None where the configuration has no window layers."""
    config, traffic = run["config"], run["traffic"]
    layers = window_layers(config)
    if layers is None:
        return None
    return dict(
        b=int(traffic["global_batch"]) // run["chips"], s=int(traffic["seq_len"]), h=layers[1],
        kv=int(config["num_key_value_heads"]), hd=int(config["head_dim"]),
        window=int(config["sliding_window"]), layers=layers[0],
    )


def least_seconds(run: dict, backward: bool) -> tuple[float, str] | None:
    """(the least time one forward call, or one backward pass, can take on the
    run's chip; which peak bounds it)."""
    z = call_shape(run)
    if z is None:
        return None
    count, moved = (backward_flops, backward_bytes_moved) if backward else (flops, bytes_moved)
    compute = count(z["b"], z["s"], z["h"], z["hd"], z["window"]) / run["peaks"]["bf16_flops_per_s"]
    memory = moved(z["b"], z["s"], z["h"], z["kv"], z["hd"]) / run["peaks"]["hbm_bytes_per_s"]
    return max(compute, memory), "compute" if compute >= memory else "memory"
