"""Plain reference for the `window_attn_moe` kind: a pre-norm decoder whose
attention layers are full causal or under a sliding window by `layer_types`,
with a head count a layer (`num_attention_heads_per_layer`), a rotary rule a
kind (`rope_parameters`), a sigmoid gate a head on the attention output, a dense
SwiGLU or routed experts with a shared one by `mlp_layer_types`, an untied head,
its next-token loss, gradients and AdamW steps, in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`.

Written from Laguna-XS.2's published configuration (`laguna`) and ISSUE 33's
equations, and imports nothing of the program; of the benchmark it takes
`reference/decoder.py`'s RMSNorm and AdamW steps and `reference/mla_moe.py`'s
matmul, SwiGLU, embedding, head and count of differing assignments.  Keys are
those of the published `config.json`.  d the hidden size, no biases, eps 1e-6:

    block:  h = x + attn_l(RMSNorm(x));  y = h + ff_l(RMSNorm(h))
    attn:   H_l query heads (48 full, 64 sliding) over 8 key/value heads of 128;
            q, k, v = x W_q, x W_k, x W_v;  q and k rotated (below);
            scores q_t . k_j / sqrt(128) over j <= t (full) or
            t - sliding_window < j <= t (sliding: the window's keys with the
            token's own);  softmax;  o_h = P_h v;  g = sigmoid(x W_g), one
            scalar a head and token;  out = concat_h(g_h o_h) W_o
    rotary: full layers: the first 64 = 128 * partial_rotary_factor dimensions
            of a head, split halves within them, the other 64 pass through;
            YaRN frequencies (`yarn_inv_freq`) and cos, sin times
            attention_factor.  Sliding layers: plain rotary over all 128.
    ff:     `dense`: a SwiGLU of `intermediate_size`.  `sparse`:
            s = sigmoid(x W_r) over all published experts; the top k by s + b (b
            a buffer without gradient); w_i = scale * s_i / (sum of the selected
            s + 1e-20); y = sum over the selected experts *held here* of
            w_i E_i(x), plus the shared expert E_s(x); each E a SwiGLU
    head:   logits = RMSNorm(y_last) W_out;  loss: mean CE(t_{i+1}) over the
            positions that have such a token

`num_experts` in the file is the number of experts held here,
`published.num_experts` the router's width, and `deployment.rank` says which
span: experts rank * held .. (rank + 1) * held - 1.  What the absent experts
would add is left out, as in the program.

Attention is computed one query head at a time under a plain `[S, S]` mask (the
causal triangle, or the band), each head recomputed in the backward pass, so
that at S 8192 one head's 268 MB of scores exist and not a group's eight.  The
routed sum is a plain loop over the held experts, each applied to every token
under a mask.  The steps are followed layer by layer as `reference/decoder.py`
does and for its reasons.  `rounding` goes around every matmul but the router's,
which is float32 in the model itself.  `follow` also counts the assignments on
which the program's selection at the seeded weights differs from this one's,
where a builder has given it the program's (`program_routing`).
"""

from __future__ import annotations

import json
import math
import sys
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from benchmarks.precision import ROUNDINGS, Rounding
from benchmarks.reference.decoder import _adam_first, _adam_second, _rms_norm, _sketch, _sumsq
from benchmarks.reference.mla_moe import (
    _leaf_key,
    _mm,
    _swiglu,
    block_params,
    differing_assignments,
    embed,
    head_logits,
    head_loss,
)

NORM_LEAVES = ("attn_norm", "mlp_norm")
ATTENTION_LEAVES = ("wq", "wk", "wv", "wo", "wg")
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTED_LEAVES = (
    "moe/router", "moe/w_gate", "moe/w_up", "moe/w_down",
    "moe/shared_gate", "moe/shared_up", "moe/shared_down",
)
# Held at its seeded value: no gradient, no update, not compared.
BUFFERS = ("moe/router_bias",)
TOP_LEAVES = ("embed", "output", "final_norm")
# Their gradient is the forward pass's result times the loss's derivative.
HEAD_LEAVES = ("output", "final_norm")
# The leaves whose shape follows the layer's head count.
PER_HEAD_LEAVES = ("wq", "wo", "wg")
_HIGH = lax.Precision.HIGHEST

# A builder may set this to `f(key, tokens) -> [blocks, B * S, k]`, the experts
# the program selects at the seeded weights.
program_routing = None
# What `follow` last counted with it (`differing_assignments`), for the notes.
last_routing = None


def sizes(cfg: dict) -> dict:
    held = int(cfg["num_experts"])
    return dict(
        d=int(cfg["hidden_size"]), KV=int(cfg["num_key_value_heads"]), hd=int(cfg["head_dim"]),
        f=int(cfg["intermediate_size"]), m=int(cfg["moe_intermediate_size"]),
        shared=int(cfg["shared_expert_intermediate_size"]), v=int(cfg["vocab_size"]),
        held=held, routed=int(cfg["published"]["num_experts"]),
        first=int(cfg["deployment"]["rank"]) * held, k=int(cfg["num_experts_per_tok"]),
        window=int(cfg["sliding_window"]),
    )


def leaf_shape(leaf: str, cfg: dict, heads: int = 0) -> tuple[int, ...]:
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    return {
        "embed": (z["v"], d), "output": (d, z["v"]), "final_norm": (d,),
        "attn_norm": (d,), "mlp_norm": (d,),
        "wq": (d, heads * hd), "wk": (d, z["KV"] * hd), "wv": (d, z["KV"] * hd),
        "wo": (heads * hd, d), "wg": (d, heads),
        "w_gate": (d, z["f"]), "w_up": (d, z["f"]), "w_down": (z["f"], d),
        "moe/router": (d, z["routed"]), "moe/router_bias": (z["routed"],),
        "moe/w_gate": (z["held"], d, z["m"]), "moe/w_up": (z["held"], d, z["m"]),
        "moe/w_down": (z["held"], z["m"], d),
        "moe/shared_gate": (d, z["shared"]), "moe/shared_up": (d, z["shared"]),
        "moe/shared_down": (z["shared"], d),
    }[leaf]


def layers(cfg: dict) -> list[tuple[str, int, str]]:
    """(mixer, query heads, feed-forward) of every layer."""
    rows = list(zip(cfg["layer_types"], cfg["num_attention_heads_per_layer"], cfg["mlp_layer_types"]))
    if len(rows) != int(cfg["num_hidden_layers"]) or any(
        len(cfg[k]) != len(rows)
        for k in ("layer_types", "num_attention_heads_per_layer", "mlp_layer_types")
    ):
        raise ValueError("the three layer lists name every one of num_hidden_layers layers")
    return rows


def blocks(cfg: dict) -> list[tuple[str, tuple[str, ...]]]:
    """(prefix, leaves that have a gradient) of every layer in forward order."""
    attention = ATTENTION_LEAVES if cfg["gating"] else ATTENTION_LEAVES[:4]
    return [
        (f"layers/{i}/", NORM_LEAVES + attention + (ROUTED_LEAVES if ff == "sparse" else DENSE_LEAVES))
        for i, (_, _, ff) in enumerate(layers(cfg))
    ]


def _buffers(leaves: tuple[str, ...]) -> tuple[str, ...]:
    return BUFFERS if "moe/router" in leaves else ()


def all_leaves(cfg: dict, buffers: bool = False) -> list[str]:
    names = list(TOP_LEAVES)
    for prefix, leaves in blocks(cfg):
        names += [prefix + n for n in leaves + (_buffers(leaves) if buffers else ())]
    return names


def leaf_kind(name: str, cfg: dict) -> tuple[str, int]:
    """`layers/3/moe/w_gate` -> (`moe/w_gate`, 0), `layers/1/wq` -> (`wq`, that
    layer's query heads): what decides a leaf's shape and scale."""
    if name in TOP_LEAVES:
        return name, 0
    tail = name.rsplit("/", 1)[-1]
    if "/moe/" in name:
        return "moe/" + tail, 0
    heads = layers(cfg)[int(name.split("/")[1])][1] if tail in PER_HEAD_LEAVES else 0
    return tail, int(heads)


def init_leaf(key: jax.Array, name: str, cfg: dict) -> jax.Array:
    """One leaf from the seed, in the type it is stored in: matrices
    n / sqrt(fan_in) in the configuration's dtype (an expert stack's fan-in is
    its middle axis, the embedding's its row), the router the same in float32,
    norm scales 1 + 0.1 n in float32.  Two leaves are drawn smaller, for the
    router's sake, as `reference/mla_moe.py` does and for its reason: the
    attention's output projection `wo` at 0.03 n / sqrt(fan_in) and the
    selection bias at 0.01 n."""
    return _draw(_leaf_key(key, name), leaf_kind(name, cfg), cfg)


def _draw(key: jax.Array, kind: tuple[str, int], cfg: dict) -> jax.Array:
    leaf, heads = kind
    shape = leaf_shape(leaf, cfg, heads)
    n = jax.random.normal(key, shape, jnp.float32)
    if leaf.endswith("norm"):
        return 1.0 + 0.1 * n
    if leaf == "moe/router_bias":
        return 0.01 * n
    fan_in = shape[1] if leaf == "embed" else shape[-2]
    dtype = jnp.float32 if leaf == "moe/router" else jnp.dtype(cfg["torch_dtype"])
    scale = 0.03 if leaf == "wo" else 1.0
    return (scale * n / math.sqrt(fan_in)).astype(dtype)


def init_params(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    return {name: init_leaf(key, name, cfg) for name in all_leaves(cfg, buffers=True)}


# --- forward ----------------------------------------------------------------


def yarn_inv_freq(rope: dict, dim: int) -> np.ndarray:
    """`transformers`' `_compute_yarn_parameters` over `dim` rotary dimensions:
    f_i = theta^(-2i / dim); low = floor(c(beta_fast)), high = ceil(c(beta_slow))
    with c(n) = dim ln(original / (2 pi n)) / (2 ln theta), clamped to
    [0, dim - 1]; r_i = clip((i - low) / (high - low), 0, 1);
    inv_freq_i = (f_i / factor) r_i + f_i (1 - r_i)."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    original = float(rope["original_max_position_embeddings"])
    i = np.arange(dim // 2, dtype=np.float64)
    f = theta ** (-2.0 * i / dim)
    c = lambda n: dim * math.log(original / (n * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(c(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(c(float(rope["beta_slow"]))), dim - 1)
    r = np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return ((f / factor) * r + f * (1.0 - r)).astype(np.float32)


def rotary_tables(rope: dict, hd: int, s: int) -> tuple[jax.Array, jax.Array]:
    """cos and sin [S, R / 2] of one kind of layer; R = hd * partial_rotary_factor."""
    dim = int(hd * float(rope.get("partial_rotary_factor", 1.0)))
    if rope["rope_type"] == "yarn":
        inv_freq, scale = yarn_inv_freq(rope, dim), float(rope["attention_factor"])
    elif rope["rope_type"] == "default":
        inv_freq = (float(rope["rope_theta"]) ** (-np.arange(0, dim, 2, dtype=np.float64) / dim))
        inv_freq, scale = inv_freq.astype(np.float32), 1.0
    else:
        raise ValueError(f"rope_type {rope['rope_type']!r} is neither yarn nor default")
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * jnp.asarray(inv_freq)
    return scale * jnp.cos(angles), scale * jnp.sin(angles)


def rotate(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x [S, heads, hd]: the first 2 * cos.shape[1] dimensions of each head
    rotate, split halves within them; the rest pass through."""
    rot = 2 * cos.shape[1]
    a, b = jnp.split(x[..., :rot], 2, axis=-1)
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., rot:]], axis=-1)


def visible(s: int, window: int | None) -> jax.Array:
    """[S, S] bool: key j is visible to query t; `t - window < j <= t`."""
    t, j = jnp.arange(s)[:, None], jnp.arange(s)[None, :]
    seen = j <= t
    return seen if window is None else jnp.logical_and(seen, j > t - window)


def _attend_head(qkv, mask, rounding):
    """One query head: q, k, v [S, hd] -> [S, hd] under the plain mask."""
    q, k, v = qkv
    scores = rounding.result(
        jnp.matmul(rounding.operand(q), rounding.operand(k).T, precision=_HIGH)
    ) / math.sqrt(q.shape[-1])
    weights = jax.nn.softmax(jnp.where(mask, scores, -jnp.inf), axis=-1)
    return rounding.result(
        jnp.matmul(rounding.operand(weights), rounding.operand(v), precision=_HIGH)
    )


def attention(q, k, v, window: int | None, rounding) -> jax.Array:
    """q [S, H, hd], k and v [S, KV, hd] -> [S, H, hd]: query head h reads
    key/value head h // (H / KV); one head at a time."""
    s, h, _ = q.shape
    group = h // k.shape[1]
    mask = visible(s, window)
    heads = (
        q.transpose(1, 0, 2),
        jnp.repeat(k.transpose(1, 0, 2), group, axis=0),
        jnp.repeat(v.transpose(1, 0, 2), group, axis=0),
    )
    out = lax.map(jax.checkpoint(partial(_attend_head, mask=mask, rounding=rounding)), heads)
    return out.transpose(1, 0, 2)


def attention_mixer(lp: dict, n: jax.Array, cfg: dict, mixer: str, rounding) -> jax.Array:
    """Gated GQA on one sequence's normalised input n [S, d], full or sliding."""
    z, mm = sizes(cfg), _mm(rounding)
    s, hd = n.shape[0], z["hd"]
    heads = lp["wq"].shape[1] // hd
    cos, sin = rotary_tables(cfg["rope_parameters"][mixer], hd, s)
    q = rotate(mm(n, lp["wq"]).reshape(s, heads, hd), cos, sin)
    k = rotate(mm(n, lp["wk"]).reshape(s, z["KV"], hd), cos, sin)
    v = mm(n, lp["wv"]).reshape(s, z["KV"], hd)
    o = attention(q, k, v, z["window"] if mixer == "sliding_attention" else None, rounding)
    if cfg["gating"]:
        o = o * jax.nn.sigmoid(mm(n, lp["wg"]))[:, :, None]
    return mm(o.reshape(s, heads * hd), lp["wo"])


def select(lp: dict, n: jax.Array, cfg: dict) -> tuple[jax.Array, jax.Array]:
    """The router on n [S, d]: (experts [S, k], weights [S, k]) over all the
    published experts, in float32 whatever the rounding."""
    s = jax.nn.sigmoid(jnp.matmul(n, lp["moe/router"].astype(jnp.float32), precision=_HIGH))
    _, chosen = lax.top_k(s + lax.stop_gradient(lp["moe/router_bias"]), sizes(cfg)["k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, float(cfg["moe_routed_scaling_factor"]) * picked


def routed_ffn(lp: dict, n: jax.Array, cfg: dict, rounding) -> tuple[jax.Array, jax.Array]:
    """sum over the selected held experts of w_i E_i(n) + E_shared(n) on one
    sequence, and the selection.  The weight is on the expert's output
    (`moe_apply_router_weight_on_input` false)."""
    z, mm = sizes(cfg), _mm(rounding)
    chosen, weights = select(lp, n, cfg)

    def one_expert(y, expert):
        w_gate, w_up, w_down, index = expert
        share = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=-1)
        return y + share[:, None] * _swiglu(mm, n, w_gate, w_up, w_down), None

    held = z["first"] + jnp.arange(z["held"])
    y, _ = lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(n),
        (lp["moe/w_gate"], lp["moe/w_up"], lp["moe/w_down"], held),
    )
    y = y + _swiglu(mm, n, lp["moe/shared_gate"], lp["moe/shared_up"], lp["moe/shared_down"])
    return y, chosen


def layer(lp: dict, x: jax.Array, cfg: dict, mixer: str, rounding=Rounding()):
    """One block on x [B, S, d], its feed-forward by the leaves it is given:
    (y, the selection [B, S, k]; zeros for a dense feed-forward)."""
    eps = float(cfg["rms_norm_eps"])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def one_sequence(xs):
        xs = xs + attention_mixer(lp, _rms_norm(xs, lp["attn_norm"], eps), cfg, mixer, rounding)
        n = _rms_norm(xs, lp["mlp_norm"], eps)
        if "moe/router" in lp:
            y, chosen = routed_ffn(lp, n, cfg, rounding)
            return xs + y, chosen
        y = _swiglu(_mm(rounding), n, lp["w_gate"], lp["w_up"], lp["w_down"])
        return xs + y, jnp.zeros((xs.shape[0], sizes(cfg)["k"]), jnp.int32)

    return lax.map(jax.checkpoint(one_sequence), x)


def forward(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> dict:
    """The whole forward pass at once, for sizes where that fits (tests):
    the logits, the loss and every routed block's selection."""
    x = embed(params["embed"], tokens)
    selected = []
    for (prefix, leaves), (mixer, _, _) in zip(blocks(cfg), layers(cfg)):
        x, chosen = layer(block_params(params, prefix), x, cfg, mixer, rounding)
        if _buffers(leaves):
            selected.append(chosen)
    norm, output = params["final_norm"].astype(jnp.float32), params["output"]
    out = {
        "main": jax.vmap(lambda xs: head_logits(norm, output, xs, cfg, rounding))(x),
        "loss": head_loss(norm, output.astype(jnp.float32), x, targets, cfg, rounding),
    }
    if selected:
        out["selected"] = jnp.stack([c.reshape(-1, sizes(cfg)["k"]) for c in selected])
    return out


def loss(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> jax.Array:
    return forward(params, tokens, targets, cfg, rounding)["loss"]


# --- the steps, layer by layer ------------------------------------------------


class _Pieces:
    """The jitted parts one configuration and precision need."""

    def __init__(self, cfg: dict, rounding):
        self.cfg = cfg
        self.blocks = [(p, leaves, mixer) for (p, leaves), (mixer, _, _) in zip(blocks(cfg), layers(cfg))]
        self.embed = jax.jit(embed)
        # one program a kind of layer: the mixer is static, the heads are a shape
        self.layer = jax.jit(partial(layer, cfg=cfg, rounding=rounding), static_argnames="mixer")
        self.head = jax.jit(
            jax.value_and_grad(partial(head_loss, cfg=cfg, rounding=rounding), argnums=(0, 1, 2))
        )

        def layer_back(lp, x, dy, mixer):
            lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
            run = partial(layer, cfg=cfg, mixer=mixer, rounding=rounding)
            _, pull, _ = jax.vjp(run, lp, x, has_aux=True)
            return pull(dy)

        self.layer_back = jax.jit(layer_back, static_argnames="mixer")
        draw = jax.jit(partial(_draw, cfg=cfg), static_argnums=1)  # one program a kind of leaf
        self.fresh = lambda key, name: draw(_leaf_key(key, name), leaf_kind(name, cfg))
        self.embed_back = jax.jit(
            lambda tokens, dx, rows: jnp.zeros((rows, dx.shape[-1]), jnp.float32).at[tokens].add(dx),
            static_argnums=2,
        )
        self.selected: list = []  # of the newest forward pass, block by block

    def gradients(self, get, tokens, targets):
        """Yield ("loss", value), then (leaf, gradient) for every leaf that
        has one: the head's, the last block's down to the first's, the table
        last.  `get(name)` returns the leaf's current value (a buffer's seeded
        one)."""

        def block_leaves(prefix, leaves):
            return {n: get(prefix + n) for n in leaves + _buffers(leaves)}

        x = self.embed(get("embed"), tokens)
        inputs, selected = [], []
        for prefix, leaves, mixer in self.blocks:
            inputs.append(x)
            x, chosen = self.layer(block_leaves(prefix, leaves), x, mixer=mixer)
            if _buffers(leaves):
                selected.append(chosen)
        value, (g_norm, g_out, dx) = self.head(
            get("final_norm").astype(jnp.float32), get("output").astype(jnp.float32), x, targets
        )
        self.selected = selected
        yield "loss", value
        yield "final_norm", g_norm
        yield "output", g_out
        del g_norm, g_out, x
        for prefix, leaves, mixer in reversed(self.blocks):
            grads, dx = self.layer_back(block_leaves(prefix, leaves), inputs.pop(), dx, mixer=mixer)
            for n in leaves:
                yield prefix + n, grads.pop(n)
        yield "embed", self.embed_back(tokens, dx, sizes(self.cfg)["v"])


@lru_cache(maxsize=4)
def _pieces(cfg_json: str, precision: str) -> _Pieces:
    """Kept so that a process that follows many seeds traces them once."""
    return _Pieces(json.loads(cfg_json), ROUNDINGS[precision])


def _decayed(name: str) -> bool:
    return not name.endswith("norm")


def follow(key, cfg: dict, batches, steps: int, *, precision: str = "float32",
           batch_sharding=None) -> dict:
    """Follow the first one or two AdamW steps from the seeded weights, as
    `reference/decoder.py` `follow` does; the same numbers come back, and
    `routing` where the program's selection is known."""
    if steps not in (1, 2):
        raise ValueError(f"the window_attn_moe reference follows 1 or 2 steps, not {steps}")
    pieces = _pieces(json.dumps(cfg, sort_keys=True), precision)
    lr, wd = float(cfg["learning_rate"]), float(cfg["weight_decay"])
    b1, b2, eps = float(cfg["adam_b1"]), float(cfg["adam_b2"]), float(cfg["adam_eps"])
    max_norm = float(cfg["grad_clip_norm"])

    def seeded(name):
        return pieces.fresh(key, name)

    def place(a):
        return jax.device_put(a, batch_sharding) if batch_sharding is not None else jnp.asarray(a)

    def norm_pass(get, tokens, targets):
        grads = pieces.gradients(get, tokens, targets)
        value = float(next(grads)[1])
        read = {}
        for name, g in grads:  # one leaf's gradient alive at a time
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
        selected = pieces.selected
        p1 = {}
        grads = pieces.gradients(seeded, tokens, targets)
        next(grads)
        for name, g in grads:
            p1[name] = _adam_first(seeded(name), g, clip1, lr, eps, wd, decay=_decayed(name))

        def stepped(name):
            return p1[name] if name in p1 else seeded(name)  # a buffer stays

        if steps == 1:
            out["update_norm"] = {
                name: math.sqrt(float(_sumsq(p - seeded(name).astype(jnp.float32))))
                for name, p in p1.items()
            }
        if steps == 2:
            tokens2, targets2 = (place(a) for a in batches[1])
            loss2, _, clip2, _ = norm_pass(stepped, tokens2, targets2)
            out["loss"].append(loss2)
            first = pieces.gradients(seeded, tokens, targets)
            second = pieces.gradients(stepped, tokens2, targets2)
            next(first), next(second)
            moved = {}
            for (name, g1), (_, g2) in zip(first, second):
                moved[name] = _adam_second(
                    seeded(name), p1[name], g1, g2, clip1, clip2,
                    lr, b1, b2, eps, wd, decay=_decayed(name),
                )
            out["update_norm"] = {k: math.sqrt(float(v)) for k, v in moved.items()}
    # Outside the reference's precision: the program selects in its own.
    if program_routing is not None and precision == "float32" and selected:
        global last_routing
        p1.clear()  # room for the program's weights
        k = sizes(cfg)["k"]
        ours = np.stack([np.asarray(c).reshape(-1, k) for c in selected])
        last_routing = out["routing"] = differing_assignments(
            ours, np.asarray(program_routing(key, tokens))
        )
        print(json.dumps({"routing": out["routing"]}), file=sys.stderr, flush=True)
    return out
