"""Plain reference for the `decoder` kind: a pre-norm decoder-only
transformer (RMSNorm, rotary positions in the split-halves convention,
grouped-query causal attention, SwiGLU, untied output head), its next-token
loss, gradients and AdamW steps, in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`.

Written from the Mistral 7B description (arXiv:2310.06825; v0.3 has no
sliding window) and the AdamW paper, and imports nothing of the program.
Keys are those of the published `config.json`.

More than a billion parameters in float32 with their gradients and two
moments do not fit beside each other in 16 GB, so the steps are followed
layer by layer: a forward pass keeps each layer's input, the backward pass
hands out one layer's gradients at a time, and whoever consumes them keeps
only sums.  Global-norm clipping needs the whole gradient's norm before any
leaf can be updated, so a step is two passes (norms, then updates), and the
second step's update needs the first gradient again, which is recomputed
in lockstep and not stored.  The seeded weights are never stored either:
`init_leaf` remakes a leaf when it is needed.  That is why `follow` stops
at two steps.

`rounding` goes around every matmul (`benchmarks/precision.py`): the identity
gives the reference, fp8 the lower-precision control.
"""

from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.precision import ROUNDINGS, Rounding
from benchmarks.sketch import sketch

LAYER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "wo", "mlp_norm", "w_gate", "w_up", "w_down",
)
TOP_LEAVES = ("embed", "output", "final_norm")
# Their gradient is the last block's output (the whole forward pass) times
# the loss's derivative: no backward pass through the blocks.
HEAD_LEAVES = ("output", "final_norm")
_HIGH = lax.Precision.HIGHEST


def leaf_shape(name: str, cfg: dict) -> tuple[int, ...]:
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    h, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    f, v = int(cfg["intermediate_size"]), int(cfg["vocab_size"])
    return {
        "embed": (v, d), "output": (d, v), "final_norm": (d,),
        "attn_norm": (d,), "mlp_norm": (d,),
        "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
        "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }[name]


def leaf_name(name: str, layer: int | None) -> str:
    return name if layer is None else f"layers/{layer}/{name}"


def all_leaves(cfg: dict) -> list[tuple[str, int | None]]:
    rows: list[tuple[str, int | None]] = [(n, None) for n in TOP_LEAVES]
    for i in range(int(cfg["num_hidden_layers"])):
        rows += [(n, i) for n in LAYER_LEAVES]
    return rows


def init_leaf(key: jax.Array, name: str, layer: int | None, cfg: dict) -> jax.Array:
    """One leaf from the seed, in the type it is stored in: matrices
    n / sqrt(fan_in) in the configuration's dtype, norm scales 1 + 0.1 n
    in float32 (all ones would leave their gradient's size to chance)."""
    index = (TOP_LEAVES + LAYER_LEAVES).index(name)
    k = jax.random.fold_in(jax.random.fold_in(key, index), 0 if layer is None else layer + 1)
    shape = leaf_shape(name, cfg)
    n = jax.random.normal(k, shape, jnp.float32)
    if name.endswith("norm"):
        return 1.0 + 0.1 * n
    return (n / math.sqrt(shape[0] if name != "embed" else shape[1])).astype(
        jnp.dtype(cfg["torch_dtype"])
    )


def init_params(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    return {
        leaf_name(n, i): init_leaf(key, n, i, cfg) for n, i in all_leaves(cfg)
    }


# --- forward ----------------------------------------------------------------


def _rms_norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [S, heads, head_dim]; rotate the two halves of each head."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attend_group(qkv, rounding):
    """One key/value head with the query heads that share it:
    q [S, G, hd], k and v [S, hd] -> [S, G, hd]."""
    q, k, v = qkv
    s, hd = k.shape
    scores = rounding.result(jnp.einsum(
        "sgd,td->gst", rounding.operand(q), rounding.operand(k), precision=_HIGH
    ))
    scores = scores / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1)
    return rounding.result(jnp.einsum(
        "gst,td->sgd", rounding.operand(weights), rounding.operand(v), precision=_HIGH
    ))


def _attention(q, k, v, rounding):
    """q [S, H, hd], k and v [S, KV, hd]: one group at a time, each
    recomputed in the backward pass, so that [S, S] scores exist for four
    heads and not for thirty-two."""
    s, h, hd = q.shape
    kv = k.shape[1]
    groups = (
        q.reshape(s, kv, h // kv, hd).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2),
        v.transpose(1, 0, 2),
    )
    out = lax.map(jax.checkpoint(partial(_attend_group, rounding=rounding)), groups)
    return out.transpose(1, 0, 2, 3).reshape(s, h * hd)


def layer(lp: dict, x: jax.Array, cfg: dict, rounding=Rounding()) -> jax.Array:
    """One decoder block on x [B, S, d]."""
    hd = int(cfg["head_dim"])
    h, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def mm(a, w):
        return rounding.result(
            jnp.matmul(rounding.operand(a), rounding.operand(w), precision=_HIGH)
        )

    def one_sequence(xs):
        n = _rms_norm(xs, lp["attn_norm"], eps)
        s = xs.shape[0]
        q = _rope(mm(n, lp["wq"]).reshape(s, h, hd), theta)
        k = _rope(mm(n, lp["wk"]).reshape(s, kv, hd), theta)
        v = mm(n, lp["wv"]).reshape(s, kv, hd)
        xs = xs + mm(_attention(q, k, v, rounding), lp["wo"])
        n = _rms_norm(xs, lp["mlp_norm"], eps)
        return xs + mm(jax.nn.silu(mm(n, lp["w_gate"])) * mm(n, lp["w_up"]), lp["w_down"])

    return lax.map(jax.checkpoint(one_sequence), x)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return table.astype(jnp.float32)[tokens]


def head_loss(final_norm, output, x, targets, cfg, rounding=Rounding()):
    """Mean next-token cross-entropy; a sequence's last position has no
    next token and is left out."""
    eps = float(cfg["rms_norm_eps"])
    w = rounding.operand(output.astype(jnp.float32))

    @jax.checkpoint
    def one_sequence(xt):
        xs, t = xt
        z = rounding.result(jnp.matmul(
            rounding.operand(_rms_norm(xs, final_norm, eps)), w, precision=_HIGH
        ))
        nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(z, t[:, None], axis=-1)[:, 0]
        return jnp.sum(nll[:-1])

    b, s = targets.shape
    return jnp.sum(lax.map(one_sequence, (x, targets))) / (b * (s - 1))


def loss(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> jax.Array:
    """The whole forward pass at once, for sizes where that fits (tests)."""
    x = embed(params["embed"], tokens)
    for i in range(int(cfg["num_hidden_layers"])):
        lp = {n: params[leaf_name(n, i)] for n in LAYER_LEAVES}
        x = layer(lp, x, cfg, rounding)
    return head_loss(params["final_norm"], params["output"], x, targets, cfg, rounding)


# --- the steps, layer by layer ------------------------------------------------


class _Pieces:
    """The jitted parts one configuration and precision need."""

    def __init__(self, cfg: dict, rounding):
        self.cfg = cfg
        self.layers = int(cfg["num_hidden_layers"])
        self.embed = jax.jit(embed)
        self.layer = jax.jit(partial(layer, cfg=cfg, rounding=rounding))
        self.head = jax.jit(
            jax.value_and_grad(partial(head_loss, cfg=cfg, rounding=rounding), argnums=(0, 1, 2))
        )

        def layer_back(lp, x, dy):
            lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
            _, pull = jax.vjp(partial(layer, cfg=cfg, rounding=rounding), lp, x)
            return pull(dy)

        self.layer_back = jax.jit(layer_back)
        self.fresh = jax.jit(partial(init_leaf, cfg=cfg), static_argnums=(1, 2))
        self.embed_back = jax.jit(
            lambda tokens, dx, rows: jnp.zeros((rows, dx.shape[-1]), jnp.float32).at[tokens].add(dx),
            static_argnums=2,
        )

    def gradients(self, get, tokens, targets):
        """Yield ("loss", None, value), then (leaf, layer, gradient) for
        every leaf, the last layer first.  `get(name, layer)` returns the
        leaf's current value."""
        x = self.embed(get("embed", None), tokens)
        inputs = []
        for i in range(self.layers):
            inputs.append(x)
            x = self.layer({n: get(n, i) for n in LAYER_LEAVES}, x)
        final_norm = get("final_norm", None).astype(jnp.float32)
        output = get("output", None).astype(jnp.float32)
        value, (g_norm, g_out, dx) = self.head(final_norm, output, x, targets)
        yield "loss", None, value
        yield "final_norm", None, g_norm
        yield "output", None, g_out
        del g_norm, g_out, x, output
        for i in reversed(range(self.layers)):
            grads, dx = self.layer_back({n: get(n, i) for n in LAYER_LEAVES}, inputs.pop(), dx)
            for n in LAYER_LEAVES:
                yield n, i, grads.pop(n)
        yield "embed", None, self.embed_back(tokens, dx, int(self.cfg["vocab_size"]))


@lru_cache(maxsize=4)
def _pieces(cfg_json: str, precision: str) -> _Pieces:
    """Kept so that a process that follows many seeds traces them once."""
    return _Pieces(json.loads(cfg_json), ROUNDINGS[precision])


def _decayed(name: str) -> bool:
    return not name.endswith("norm")


@jax.jit
def _sumsq(x):
    return jnp.sum(jnp.square(x.astype(jnp.float32)))


_sketch = jax.jit(sketch, static_argnums=1)


@partial(jax.jit, static_argnames=("decay",))
def _adam_first(p0, g, clip, lr, eps, wd, decay):
    """AdamW's first step in closed form: both corrected moments are the
    clipped gradient and its square.  Returns p1 in float32."""
    p0 = p0.astype(jnp.float32)
    g = g * clip
    direction = g / (jnp.abs(g) + eps)
    if decay:
        direction = direction + wd * p0
    return p0 - lr * direction


@partial(jax.jit, static_argnames=("decay",))
def _adam_second(p0, p1, g1, g2, clip1, clip2, lr, b1, b2, eps, wd, decay):
    """AdamW's second step from both gradients; returns the sum of squares
    of p2 - p0."""
    p0 = p0.astype(jnp.float32)
    g1, g2 = g1 * clip1, g2 * clip2
    mu = b1 * (1 - b1) * g1 + (1 - b1) * g2
    nu = b2 * (1 - b2) * jnp.square(g1) + (1 - b2) * jnp.square(g2)
    direction = (mu / (1 - b1**2)) / (jnp.sqrt(nu / (1 - b2**2)) + eps)
    if decay:
        direction = direction + wd * p1
    return jnp.sum(jnp.square(p1 - lr * direction - p0))


def follow(key, cfg: dict, batches, steps: int, *, precision: str = "float32",
           batch_sharding=None) -> dict:
    """Follow the first one or two AdamW steps from the seeded weights.

    Returns each step's loss, the norm and the seeded projection
    (`sketch.py`) per leaf of the first gradient as AdamW gets it (after
    clipping), and the norm per leaf of the parameters' change after the
    last step, as Python floats.
    """
    if steps not in (1, 2):
        raise ValueError(f"the decoder reference follows 1 or 2 steps, not {steps}")
    pieces = _pieces(json.dumps(cfg, sort_keys=True), precision)
    lr, wd = float(cfg["learning_rate"]), float(cfg["weight_decay"])
    b1, b2, eps = float(cfg["adam_b1"]), float(cfg["adam_b2"]), float(cfg["adam_eps"])
    max_norm = float(cfg["grad_clip_norm"])

    def seeded(name, layer):
        return pieces.fresh(key, name, layer)

    def place(a):
        return jax.device_put(a, batch_sharding) if batch_sharding is not None else jnp.asarray(a)

    def norm_pass(get, tokens, targets):
        grads = pieces.gradients(get, tokens, targets)
        value = float(next(grads)[2])
        read = {}
        for n, i, g in grads:  # one leaf's gradient alive at a time
            name = leaf_name(n, i)
            read[name] = (_sumsq(g), _sketch(g, name, key))
        sumsq = {k: float(v) for k, (v, _) in read.items()}
        projected = {k: [float(x) for x in v] for k, (_, v) in read.items()}
        total = math.sqrt(sum(sumsq.values()))
        return value, sumsq, min(1.0, max_norm / total), projected

    with jax.default_matmul_precision("highest"):
        tokens, targets = (place(a) for a in batches[0])
        loss1, sumsq1, clip1, sketch1 = norm_pass(seeded, tokens, targets)
        out = {
            "loss": [loss1],
            "grad_norm": {k: clip1 * math.sqrt(v) for k, v in sumsq1.items()},
            "grad_sketch": {k: [clip1 * x for x in v] for k, v in sketch1.items()},
            "head_leaves": list(HEAD_LEAVES),
        }
        p1 = {}
        grads = pieces.gradients(seeded, tokens, targets)
        next(grads)
        for n, i, g in grads:
            p1[(n, i)] = _adam_first(
                seeded(n, i), g, clip1, lr, eps, wd, decay=_decayed(n)
            )
        if steps == 1:
            out["update_norm"] = {
                leaf_name(n, i): math.sqrt(float(_sumsq(p - seeded(n, i).astype(jnp.float32))))
                for (n, i), p in p1.items()
            }
            return out
        tokens2, targets2 = (place(a) for a in batches[1])
        loss2, _, clip2, _ = norm_pass(lambda n, i: p1[(n, i)], tokens2, targets2)
        out["loss"].append(loss2)
        first = pieces.gradients(seeded, tokens, targets)
        second = pieces.gradients(lambda n, i: p1[(n, i)], tokens2, targets2)
        next(first), next(second)
        moved = {}
        for (n, i, g1), (_, _, g2) in zip(first, second):
            moved[leaf_name(n, i)] = _adam_second(
                seeded(n, i), p1[(n, i)], g1, g2, clip1, clip2,
                lr, b1, b2, eps, wd, decay=_decayed(n),
            )
        out["update_norm"] = {k: math.sqrt(float(v)) for k, v in moved.items()}
        return out
