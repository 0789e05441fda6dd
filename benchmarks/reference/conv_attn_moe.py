"""Plain reference for the `conv_attn_moe` kind: a pre-norm decoder whose
layers take their token mixer from `layer_types` (a gated short convolution or
grouped-query attention with normalised heads) and their feed-forward from
`num_dense_layers` (a dense SwiGLU first, routed experts after), its next-token
loss through the tied table, gradients and AdamW steps, in `jax.numpy`, float32,
under `jax.default_matmul_precision("highest")`.

Written from LFM2's published configuration (`lfm2_moe`: `Lfm2ShortConv`,
`Lfm2MoeAttention`, `Lfm2MoeSparseMoeBlock`), and imports nothing of the
program; of the benchmark it takes `reference/decoder.py`'s RMSNorm, rotary
embedding, head-by-head attention and AdamW steps and `reference/mla_moe.py`'s
matmul, SwiGLU and count of differing assignments.  Keys are those of the
published `config.json`.  d the hidden size, no biases:

    block:  h = x + mixer_i(RMSNorm(x));  y = h + ff_i(RMSNorm(h))
    conv:   [B | C | u] = x W_in (the three thirds in that order);  z = B * u;
            c_t = sum_{j < L} w[j] * z[t - (L - 1) + j], z zero before the
            sequence's start (depthwise, causal, L = conv_L_cache taps a
            channel);  out = (C * c) W_out
    attn:   q, k, v = x W_q, x W_k, x W_v per head of d / heads;
            q = RMSNorm(q), k = RMSNorm(k) over the head (one scale for all
            query heads, one for all key heads); both rotated, split halves;
            scores / sqrt(head size), causal softmax, o = concat(P v) W_o
    ff:     layers before `num_dense_layers` a SwiGLU of `intermediate_size`;
            every other layer s = sigmoid(x W_r) over all experts; the top k by
            s + b (b a buffer without gradient); w_i = scale * s_i / (sum of
            the selected s + 1e-6); y = sum over the selected experts *held
            here* of w_i E_i(x), each E a SwiGLU of `moe_intermediate_size`;
            no shared expert
    head:   logits = RMSNorm(y_last) Emb^T;  loss: mean CE(t_{i+1}) over the
            positions that have such a token

Departures from the published model, each also under the configuration's
`assumed`: the filter is stored taps-major, `[L, d]` (published `[d, 1, L]`);
the rotary embedding is split halves (with seeded weights the published
interleaving is a permutation of columns); the table is tied to the head;
`num_experts` in the file is the number of experts held here,
`published.num_experts` the router's width, and `deployment.rank` says which
span: experts rank * held .. (rank + 1) * held - 1.  What the absent experts
would add is left out, as in the program.

The convolution is an explicit sum over its taps, one shifted copy of z a tap.
The routed sum is a plain loop over the held experts, each applied to every
token under a mask.  The steps are followed layer by layer as
`reference/decoder.py` does and for its reasons.  `rounding` goes around every
matmul but the router's, which is float32 in the model itself; the taps and the
gates are elementwise float32 on both sides and are not rounded.  `follow` also
counts the assignments on which the program's selection at the seeded weights
differs from this one's, where a builder has given it the program's
(`program_routing`), prints the count and returns it under `routing`.
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
from benchmarks.reference.decoder import (
    _adam_first,
    _adam_second,
    _attention,
    _rms_norm,
    _rope,
    _sketch,
    _sumsq,
)
from benchmarks.reference.mla_moe import (
    _leaf_key,
    _mm,
    _swiglu,
    block_params,
    differing_assignments,
    embed,
)

NORM_LEAVES = ("operator_norm", "ffn_norm")
MIXER_LEAVES = {
    "conv": ("conv_in", "conv_w", "conv_out"),
    "full_attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm"),
}
DENSE_LEAVES = ("w_gate", "w_up", "w_down")
ROUTED_LEAVES = ("moe/router", "moe/w_gate", "moe/w_up", "moe/w_down")
# Held at its seeded value: no gradient, no update, not compared.
BUFFERS = ("moe/router_bias",)
TOP_LEAVES = ("embed", "final_norm")
# Its gradient is the forward pass's result times the loss's derivative.  The
# table is not among them: it is tied, so its gradient has the embedding's
# part, which has come back through every block and every differing choice of
# an expert (it reads like a block's leaf: 0.21-0.31 where this reads 0.04).
HEAD_LEAVES = ("final_norm",)
_HIGH = lax.Precision.HIGHEST

# A builder may set this to `f(key, tokens) -> [blocks, B * S, k]`, the experts
# the program selects at the seeded weights.
program_routing = None
# What `follow` last counted with it (`differing_assignments`), for the notes.
last_routing = None


def sizes(cfg: dict) -> dict:
    held = int(cfg["num_experts"])
    d, H = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    return dict(
        d=d, H=H, KV=int(cfg["num_key_value_heads"]), hd=d // H, taps=int(cfg["conv_L_cache"]),
        f=int(cfg["intermediate_size"]), m=int(cfg["moe_intermediate_size"]),
        v=int(cfg["vocab_size"]), held=held, routed=int(cfg["published"]["num_experts"]),
        first=int(cfg["deployment"]["rank"]) * held, k=int(cfg["num_experts_per_tok"]),
        dense_layers=int(cfg["num_dense_layers"]),
    )


def leaf_shape(leaf: str, cfg: dict) -> tuple[int, ...]:
    z = sizes(cfg)
    d, hd = z["d"], z["hd"]
    return {
        "embed": (z["v"], d), "final_norm": (d,), "operator_norm": (d,), "ffn_norm": (d,),
        "conv_in": (d, 3 * d), "conv_w": (z["taps"], d), "conv_out": (d, d),
        "wq": (d, z["H"] * hd), "wk": (d, z["KV"] * hd), "wv": (d, z["KV"] * hd),
        "wo": (z["H"] * hd, d), "q_norm": (hd,), "k_norm": (hd,),
        "w_gate": (d, z["f"]), "w_up": (d, z["f"]), "w_down": (z["f"], d),
        "moe/router": (d, z["routed"]), "moe/router_bias": (z["routed"],),
        "moe/w_gate": (z["held"], d, z["m"]), "moe/w_up": (z["held"], d, z["m"]),
        "moe/w_down": (z["held"], z["m"], d),
    }[leaf]


def blocks(cfg: dict) -> list[tuple[str, tuple[str, ...]]]:
    """(prefix, leaves that have a gradient) of every layer in forward order."""
    if len(cfg["layer_types"]) != int(cfg["num_hidden_layers"]):
        raise ValueError("layer_types names every one of num_hidden_layers layers")
    dense = sizes(cfg)["dense_layers"]
    return [
        (f"layers/{i}/", NORM_LEAVES + MIXER_LEAVES[mixer] + (DENSE_LEAVES if i < dense else ROUTED_LEAVES))
        for i, mixer in enumerate(cfg["layer_types"])
    ]


def _buffers(leaves: tuple[str, ...]) -> tuple[str, ...]:
    return BUFFERS if "moe/router" in leaves else ()


def all_leaves(cfg: dict, buffers: bool = False) -> list[str]:
    names = list(TOP_LEAVES)
    for prefix, leaves in blocks(cfg):
        names += [prefix + n for n in leaves + (_buffers(leaves) if buffers else ())]
    return names


def leaf_kind(name: str) -> str:
    """`layers/3/moe/w_gate` -> `moe/w_gate`, `layers/0/conv_in` -> `conv_in`."""
    if name in TOP_LEAVES:
        return name
    tail = name.rsplit("/", 1)[-1]
    return "moe/" + tail if "/moe/" in name else tail


def init_leaf(key: jax.Array, name: str, cfg: dict) -> jax.Array:
    """One leaf from the seed, in the type it is stored in: matrices
    n / sqrt(fan_in) in the configuration's dtype (an expert stack's fan-in is
    its middle axis, the embedding's its row, the filter's its taps), the
    router the same in float32, norm scales 1 + 0.1 n in float32.

    Three leaves are drawn smaller, for the router's sake, as
    `reference/mla_moe.py` does and for its reason: a mixer's output on seeded
    weights is several times an embedding row of 1 / sqrt(d) (larger rows are
    beyond what a bfloat16 weight can take an AdamW step of 3e-4 on), and
    causal attention's is nearly the same vector for every token, which then
    decides the router's choice for all of them alike.  So both mixers' output
    projections (`conv_out`, `wo`) are 0.03 n / sqrt(fan_in) and the selection
    bias 0.01 n."""
    return _draw(_leaf_key(key, name), leaf_kind(name), cfg)


def _draw(key: jax.Array, leaf: str, cfg: dict) -> jax.Array:
    shape = leaf_shape(leaf, cfg)
    n = jax.random.normal(key, shape, jnp.float32)
    if leaf.endswith("norm"):
        return 1.0 + 0.1 * n
    if leaf == "moe/router_bias":
        return 0.01 * n
    fan_in = {"embed": shape[1], "conv_w": shape[0]}.get(leaf, shape[-2])
    dtype = jnp.float32 if leaf == "moe/router" else jnp.dtype(cfg["torch_dtype"])
    scale = 0.03 if leaf in ("conv_out", "wo") else 1.0
    return (scale * n / math.sqrt(fan_in)).astype(dtype)


def init_params(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    return {name: init_leaf(key, name, cfg) for name in all_leaves(cfg, buffers=True)}


# --- forward ----------------------------------------------------------------


def short_conv(z: jax.Array, w: jax.Array) -> jax.Array:
    """z [S, d], w [L, d] -> c[t] = sum_j w[j] * z[t - (L - 1) + j], with
    z zero before the start: one shifted copy of z a tap."""
    taps, s = w.shape[0], z.shape[0]
    c = jnp.zeros_like(z)
    for j in range(taps):
        back = taps - 1 - j  # how far behind t this tap reads
        shifted = jnp.concatenate([jnp.zeros((back, z.shape[1]), z.dtype), z[: s - back]])
        c = c + w[j][None, :] * shifted
    return c


def conv_mixer(lp: dict, n: jax.Array, cfg: dict, rounding) -> jax.Array:
    """The gated short convolution on one sequence's normalised input n [S, d]."""
    mm = _mm(rounding)
    b, c, u = jnp.split(mm(n, lp["conv_in"]), 3, axis=-1)
    return mm(c * short_conv(b * u, lp["conv_w"]), lp["conv_out"])


def attention_mixer(lp: dict, n: jax.Array, cfg: dict, rounding) -> jax.Array:
    """Causal GQA with normalised query and key heads on one sequence n [S, d]."""
    z, mm = sizes(cfg), _mm(rounding)
    eps, theta = float(cfg["norm_eps"]), float(cfg["rope_theta"])
    s = n.shape[0]
    q = _rms_norm(mm(n, lp["wq"]).reshape(s, z["H"], z["hd"]), lp["q_norm"], eps)
    k = _rms_norm(mm(n, lp["wk"]).reshape(s, z["KV"], z["hd"]), lp["k_norm"], eps)
    v = mm(n, lp["wv"]).reshape(s, z["KV"], z["hd"])
    return mm(_attention(_rope(q, theta), _rope(k, theta), v, rounding), lp["wo"])


def select(lp: dict, n: jax.Array, cfg: dict) -> tuple[jax.Array, jax.Array]:
    """The router on n [S, d]: (experts [S, k], weights [S, k]) over all the
    published experts, in float32 whatever the rounding."""
    s = jax.nn.sigmoid(jnp.matmul(n, lp["moe/router"].astype(jnp.float32), precision=_HIGH))
    choice = s + lax.stop_gradient(lp["moe/router_bias"]) if cfg["use_expert_bias"] else s
    _, chosen = lax.top_k(choice, sizes(cfg)["k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return chosen, float(cfg["routed_scaling_factor"]) * picked


def routed_ffn(lp: dict, n: jax.Array, cfg: dict, rounding) -> tuple[jax.Array, jax.Array]:
    """sum over the selected held experts of w_i E_i(n) on one sequence, and
    the selection."""
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
    return y, chosen


def layer(lp: dict, x: jax.Array, cfg: dict, rounding=Rounding()) -> tuple[jax.Array, jax.Array]:
    """One block on x [B, S, d], its mixer and feed-forward by the leaves it is
    given: (y, the selection [B, S, k]; zeros for a dense feed-forward)."""
    eps = float(cfg["norm_eps"])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    mixer = conv_mixer if "conv_in" in lp else attention_mixer

    def one_sequence(xs):
        xs = xs + mixer(lp, _rms_norm(xs, lp["operator_norm"], eps), cfg, rounding)
        n = _rms_norm(xs, lp["ffn_norm"], eps)
        if "moe/router" in lp:
            y, chosen = routed_ffn(lp, n, cfg, rounding)
            return xs + y, chosen
        y = _swiglu(_mm(rounding), n, lp["w_gate"], lp["w_up"], lp["w_down"])
        return xs + y, jnp.zeros((xs.shape[0], sizes(cfg)["k"]), jnp.int32)

    return lax.map(jax.checkpoint(one_sequence), x)


def head_logits(norm, table, x, cfg, rounding=Rounding()):
    """[S, d] -> [S, V] of one sequence, through the table transposed."""
    return rounding.result(jnp.matmul(
        rounding.operand(_rms_norm(x, norm, float(cfg["norm_eps"]))),
        rounding.operand(table.astype(jnp.float32).T), precision=_HIGH,
    ))


def head_loss(norm, table, x, targets, cfg, rounding=Rounding()):
    """Mean next-token cross-entropy; a sequence's last position holds a
    wrapped token and is left out."""

    @jax.checkpoint
    def one_sequence(xt):
        xs, t = xt
        z = head_logits(norm, table, xs, cfg, rounding)
        nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(z, t[:, None], axis=-1)[:, 0]
        return jnp.sum(nll[:-1])

    b, s = targets.shape
    return jnp.sum(lax.map(one_sequence, (x, targets))) / (b * (s - 1))


def forward(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> dict:
    """The whole forward pass at once, for sizes where that fits (tests):
    the logits, the loss and every routed block's selection."""
    x = embed(params["embed"], tokens)
    selected = []
    for prefix, leaves in blocks(cfg):
        x, chosen = layer(block_params(params, prefix), x, cfg, rounding)
        if _buffers(leaves):
            selected.append(chosen)
    norm = params["final_norm"].astype(jnp.float32)
    out = {
        "main": jax.vmap(lambda xs: head_logits(norm, params["embed"], xs, cfg, rounding))(x),
        "loss": head_loss(norm, params["embed"], x, targets, cfg, rounding),
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
        self.blocks = blocks(cfg)
        self.embed = jax.jit(embed)
        self.layer = jax.jit(partial(layer, cfg=cfg, rounding=rounding))
        self.head = jax.jit(
            jax.value_and_grad(partial(head_loss, cfg=cfg, rounding=rounding), argnums=(0, 1, 2))
        )

        def layer_back(lp, x, dy):
            lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
            _, pull, _ = jax.vjp(partial(layer, cfg=cfg, rounding=rounding), lp, x, has_aux=True)
            return pull(dy)

        self.layer_back = jax.jit(layer_back)
        draw = jax.jit(partial(_draw, cfg=cfg), static_argnums=1)  # one program a kind of leaf
        self.fresh = lambda key, name: draw(_leaf_key(key, name), leaf_kind(name))
        self.embed_back = jax.jit(lambda g_table, tokens, dx: g_table.at[tokens].add(dx))
        self.selected: list = []  # of the newest forward pass, block by block

    def gradients(self, get, tokens, targets):
        """Yield ("loss", value), then (leaf, gradient) for every leaf that
        has one: the final norm, the last block's down to the first's, and the
        table (the head's part and the embedding's) last.  `get(name)` returns
        the leaf's current value (a buffer's seeded one)."""

        def block_leaves(prefix, leaves):
            return {n: get(prefix + n) for n in leaves + _buffers(leaves)}

        x = self.embed(get("embed"), tokens)
        inputs, selected = [], []
        for prefix, leaves in self.blocks:
            inputs.append(x)
            x, chosen = self.layer(block_leaves(prefix, leaves), x)
            if _buffers(leaves):
                selected.append(chosen)
        final_norm = get("final_norm").astype(jnp.float32)
        value, (g_norm, g_table, dx) = self.head(
            final_norm, get("embed").astype(jnp.float32), x, targets
        )
        self.selected = selected
        yield "loss", value
        yield "final_norm", g_norm
        del g_norm, x
        for prefix, leaves in reversed(self.blocks):
            grads, dx = self.layer_back(block_leaves(prefix, leaves), inputs.pop(), dx)
            for n in leaves:
                yield prefix + n, grads.pop(n)
        yield "embed", self.embed_back(g_table, tokens, dx)


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
        raise ValueError(f"the conv_attn_moe reference follows 1 or 2 steps, not {steps}")
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
