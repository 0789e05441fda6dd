"""Plain reference for the `ssm_attn_moe` kind: a pre-norm decoder whose blocks
are half layers, each a residual around one part by `hybrid_override_pattern`
(`M` a Mamba-2 mixer, `*` grouped-query attention, `E` routed latent experts
with a shared one), its next-token loss through an untied head, gradients and
AdamW steps, in `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`.

Written from Nemotron-3-Super's published configuration (`nemotron_h`) and the
Mamba-2 paper's recurrence (arXiv:2405.21060), and imports nothing of the
program; of the benchmark it takes `reference/decoder.py`'s RMSNorm, attention
of one key/value head and AdamW steps, `reference/mla_moe.py`'s matmul and count
of differing assignments and `reference/conv_attn_moe.py`'s causal taps.  Keys
are those of the published `config.json`.  d the hidden size, no biases but
the convolution's:

    block:  y = x + part(RMSNorm(x)), eps `layer_norm_epsilon`
    M:      [z | xBC | dt] = n W_in (inner, inner + 2 G N, H; inner = H P);
            xBC = silu(conv(xBC) + b), conv depthwise and causal over
            `conv_kernel` taps, zeros before the start; xBC parts into
            x [H, P], B [G, N], C [G, N], a head reading the B and C of its
            group of H / G;  dt = softplus(dt + dt_bias) (no clamp),
            A = -exp(A_log) a head;  a token at a time, h in [H, P, N]:
                h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t
                y_t = h_t C_t + D x_t
            y = RMSNorm(y * silu(z)) * w over each of the G groups of inner / G
            channels (the gate first);  out = y W_out
    *:      q, k, v = n W_q, n W_k, n W_v per head of `head_dim`; no positional
            encoding; scores / sqrt(head size), causal softmax, o = concat(P v) W_o
    E:      s = sigmoid(n W_r) over all experts; the top k by s + b (b a buffer
            without gradient); w_i = scale * s_i / (sum of the selected s);
            l = n W_in (d -> `moe_latent_size`);
            routed = sum over the selected experts *held here* of
            w_i W2_i relu(W1_i l)^2;
            out = routed W_out (latent -> d) + W2s relu(W1s n)^2 (the shared one)
    head:   logits = RMSNorm(y_last) W_out;  loss: mean CE(t_{i+1}) over the
            positions that have such a token

Departures from the published model, each also under the configuration's
`assumed`: no rotary embedding in the attention blocks; no bias and no norm on
the two latent projections; the shared expert reads the full width; the
multi-token prediction module is left out; the filter is stored taps-major;
`n_routed_experts` in the file is the number of experts held here,
`published.n_routed_experts` the router's width, and `deployment.rank` says
which span.  What the absent experts would add is left out, as in the program.

The scan is its definition, the recurrence a token at a time: a `scan` over
chunks of a rematerialised inner `scan`, so that its gradient holds a state a
chunk and a chunk's states, not a state a token.  Attention is four query heads
at a time (16 share a key/value head).  The routed sum is a plain loop over the
held experts, each applied to every token under a mask.  `rounding` goes around
every matmul but the router's, which is float32 in the model itself; the
recurrence, the taps and the gates are elementwise float32 on both sides and
are not rounded.  `follow` also counts the assignments on which the program's
selection at the seeded weights differs from this one's, where a builder has
given it the program's (`program_routing`).
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
from benchmarks.reference.conv_attn_moe import short_conv
from benchmarks.reference.decoder import (
    _adam_first,
    _adam_second,
    _attend_group,
    _rms_norm,
    _sketch,
    _sumsq,
)
from benchmarks.reference.mla_moe import _leaf_key, _mm, block_params, differing_assignments, embed

BLOCK_LEAVES = {
    "M": ("norm", "in_proj", "conv_w", "conv_bias", "dt_bias", "A_log", "D", "gate_norm", "out_proj"),
    "*": ("norm", "wq", "wk", "wv", "wo"),
    "E": ("norm", "moe/router", "moe/w_up", "moe/w_down", "latent_in", "latent_out",
          "shared_up", "shared_down"),
}
# Held at its seeded value: no gradient, no update, not compared.
BUFFERS = ("moe/router_bias",)
TOP_LEAVES = ("embed", "output", "final_norm")
# Their gradient is the forward pass's result times the loss's derivative.
HEAD_LEAVES = ("output", "final_norm")
# Seeded with their columns centred over the expert's hidden units (`init_leaf`).
CENTRED = ("shared_down", "moe/w_down")
# Query heads whose [S, S] scores exist at once.
HEADS_AT_ONCE = 4
_HIGH = lax.Precision.HIGHEST

# A builder may set this to `f(key, tokens) -> [blocks, B * S, k]`, the experts
# the program selects at the seeded weights.
program_routing = None
# What `follow` last counted with it (`differing_assignments`), for the notes.
last_routing = None


def sizes(cfg: dict) -> dict:
    held = int(cfg["n_routed_experts"])
    H, P = int(cfg["mamba_num_heads"]), int(cfg["mamba_head_dim"])
    G, N = int(cfg["n_groups"]), int(cfg["ssm_state_size"])
    return dict(
        d=int(cfg["hidden_size"]), H=H, P=P, G=G, N=N, inner=H * P, conv=H * P + 2 * G * N,
        taps=int(cfg["conv_kernel"]), chunk=int(cfg["chunk_size"]),
        heads=int(cfg["num_attention_heads"]), KV=int(cfg["num_key_value_heads"]),
        hd=int(cfg["head_dim"]), latent=int(cfg["moe_latent_size"]),
        m=int(cfg["moe_intermediate_size"]), shared=int(cfg["moe_shared_expert_intermediate_size"]),
        v=int(cfg["vocab_size"]), held=held, routed=int(cfg["published"]["n_routed_experts"]),
        first=int(cfg["deployment"]["rank"]) * held, k=int(cfg["num_experts_per_tok"]),
    )


def leaf_shape(leaf: str, cfg: dict) -> tuple[int, ...]:
    z = sizes(cfg)
    d, q, kv = z["d"], z["heads"] * z["hd"], z["KV"] * z["hd"]
    return {
        "embed": (z["v"], d), "output": (d, z["v"]), "final_norm": (d,), "norm": (d,),
        "in_proj": (d, z["inner"] + z["conv"] + z["H"]), "conv_w": (z["taps"], z["conv"]),
        "conv_bias": (z["conv"],), "dt_bias": (z["H"],), "A_log": (z["H"],), "D": (z["H"],),
        "gate_norm": (z["inner"],), "out_proj": (z["inner"], d),
        "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
        "moe/router": (d, z["routed"]), "moe/router_bias": (z["routed"],),
        "moe/w_up": (z["held"], z["latent"], z["m"]), "moe/w_down": (z["held"], z["m"], z["latent"]),
        "latent_in": (d, z["latent"]), "latent_out": (z["latent"], d),
        "shared_up": (d, z["shared"]), "shared_down": (z["shared"], d),
    }[leaf]


def pattern(cfg: dict) -> str:
    blocks_ = cfg["hybrid_override_pattern"]
    if len(blocks_) != int(cfg["num_hidden_layers"]) or set(blocks_) - set(BLOCK_LEAVES):
        raise ValueError("hybrid_override_pattern names every one of num_hidden_layers blocks")
    return blocks_


def blocks(cfg: dict) -> list[tuple[str, tuple[str, ...]]]:
    """(prefix, leaves that have a gradient) of every block in forward order."""
    return [(f"layers/{i}/", BLOCK_LEAVES[b]) for i, b in enumerate(pattern(cfg))]


def _buffers(leaves: tuple[str, ...]) -> tuple[str, ...]:
    return BUFFERS if "moe/router" in leaves else ()


def all_leaves(cfg: dict, buffers: bool = False) -> list[str]:
    names = list(TOP_LEAVES)
    for prefix, leaves in blocks(cfg):
        names += [prefix + n for n in leaves + (_buffers(leaves) if buffers else ())]
    return names


def leaf_kind(name: str) -> str:
    """`layers/2/moe/w_up` -> `moe/w_up`, `layers/1/in_proj` -> `in_proj`."""
    if name in TOP_LEAVES:
        return name
    tail = name.rsplit("/", 1)[-1]
    return "moe/" + tail if "/moe/" in name else tail


def init_leaf(key: jax.Array, name: str, cfg: dict) -> jax.Array:
    """One leaf from the seed, in the type it is stored in: matrices
    n / sqrt(fan_in) in the configuration's dtype (an expert stack's fan-in is
    its middle axis, the embedding's its row, the filter's its taps), the
    router the same in float32, norm scales 1 + 0.1 n in float32.

    The state-space leaves get the decays a trained model has, so that a state
    carried over 64 chunks is what the check reads: `A_log` the log of A
    uniform in [1, 16]; `dt_bias` the softplus' inverse of dt log-uniform in
    [`time_step_min`, `time_step_max`], floored at `time_step_floor`; `D` 1;
    the convolution's bias 0.1 n; all float32.

    The mixers' output projections (`out_proj`, `wo`) are 0.03 n / sqrt(fan_in)
    and the selection bias 0.01 n, as the sibling references draw theirs and
    for their reason: a causal mixer's output on seeded weights is nearly the
    same vector for every token and several times an embedding row, and
    would decide the router's choice for all of them alike.

    The experts' second matrices (`shared_down`, `moe/w_down`) are drawn with
    each column's mean over the hidden units taken off.  relu^2 is never
    negative, so an uncentred one adds 0.5 sum_j w[j, :] to every token alike,
    a sixth of the block's output by its square; after the first `E` block that
    vector is in every token's router input and the selection follows it: at
    these widths the most chosen of 512 experts got 7 to 9 times its share in
    blocks 2 to 5, and 1.7 to 2.0 centred, which is what 44 choices an expert
    scatter by themselves (PERF.md section 4).  A trained router is balanced by
    its bias; seeded weights have to be balanced by construction."""
    return _draw(_leaf_key(key, name), leaf_kind(name), cfg)


def _draw(key: jax.Array, leaf: str, cfg: dict) -> jax.Array:
    shape = leaf_shape(leaf, cfg)
    if leaf == "A_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if leaf == "dt_bias":
        low, high = math.log(float(cfg["time_step_min"])), math.log(float(cfg["time_step_max"]))
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
        dt = jnp.maximum(dt, float(cfg["time_step_floor"]))
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "D":
        return jnp.ones(shape, jnp.float32)
    n = jax.random.normal(key, shape, jnp.float32)
    if leaf.endswith("norm"):
        return 1.0 + 0.1 * n
    if leaf == "conv_bias":
        return 0.1 * n
    if leaf == "moe/router_bias":
        return 0.01 * n
    fan_in = {"embed": shape[1], "conv_w": shape[0]}.get(leaf, shape[-2])
    dtype = jnp.float32 if leaf == "moe/router" else jnp.dtype(cfg["torch_dtype"])
    scale = 0.03 if leaf in ("out_proj", "wo") else 1.0
    if leaf in CENTRED:
        n = n - jnp.mean(n, axis=-2, keepdims=True)
    return (scale * n / math.sqrt(fan_in)).astype(dtype)


def init_params(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    return {name: init_leaf(key, name, cfg) for name in all_leaves(cfg, buffers=True)}


# --- forward ----------------------------------------------------------------


def recurrence(x, dt, A, B, C, D, chunk: int) -> jax.Array:
    """The state-space scan as it is defined, on one sequence: x [S, H, P],
    dt [S, H], A and D [H], B and C [S, G, N] -> y [S, H, P], the state
    h [H, P, N] stepping a token at a time.  The tokens go `chunk` at a time
    through a rematerialised inner `scan`; a tail is padded with dt = 0, which
    leaves the state as it is."""
    S, H, _ = x.shape
    G = B.shape[1]
    pad = -S % chunk

    def chunks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape(((S + pad) // chunk, chunk) + a.shape[1:])

    def token(h, t):
        xt, dtt, Bt, Ct = t
        Bt, Ct = jnp.repeat(Bt, H // G, axis=0), jnp.repeat(Ct, H // G, axis=0)  # [H, N]
        h = jnp.exp(dtt * A)[:, None, None] * h + (dtt[:, None] * xt)[:, :, None] * Bt[:, None, :]
        return h, jnp.einsum("hpn,hn->hp", h, Ct, precision=_HIGH) + D[:, None] * xt

    @jax.checkpoint
    def one_chunk(h, c):
        return lax.scan(token, h, c)

    h0 = jnp.zeros(x.shape[1:] + B.shape[2:], jnp.float32)
    _, y = lax.scan(one_chunk, h0, tuple(chunks(a) for a in (x, dt, B, C)))
    return y.reshape((S + pad,) + x.shape[1:])[:S]


def ssm_mixer(lp: dict, n: jax.Array, cfg: dict, rounding) -> jax.Array:
    """Mamba-2 on one sequence's normalised input n [S, d]."""
    z, mm = sizes(cfg), _mm(rounding)
    s, inner, G, N = n.shape[0], z["inner"], z["G"], z["N"]
    gate, xBC, dt = jnp.split(mm(n, lp["in_proj"]), (inner, inner + z["conv"]), axis=-1)
    xBC = jax.nn.silu(short_conv(xBC, lp["conv_w"]) + lp["conv_bias"])
    x, B, C = jnp.split(xBC, (inner, inner + G * N), axis=-1)
    y = recurrence(
        x.reshape(s, z["H"], z["P"]), jax.nn.softplus(dt + lp["dt_bias"]), -jnp.exp(lp["A_log"]),
        B.reshape(s, G, N), C.reshape(s, G, N), lp["D"], z["chunk"],
    )
    gated = (y.reshape(s, inner) * jax.nn.silu(gate)).reshape(s, G, inner // G)
    normed = gated * lax.rsqrt(
        jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + float(cfg["layer_norm_epsilon"])
    )
    return mm(normed.reshape(s, inner) * lp["gate_norm"], lp["out_proj"])


def attention_mixer(lp: dict, n: jax.Array, cfg: dict, rounding) -> jax.Array:
    """Causal GQA without positions on one sequence n [S, d], `HEADS_AT_ONCE`
    query heads of one key/value head at a time, each recomputed in the
    backward pass."""
    z, mm = sizes(cfg), _mm(rounding)
    s, H, KV, hd = n.shape[0], z["heads"], z["KV"], z["hd"]
    g = math.gcd(H // KV, HEADS_AT_ONCE)
    parts = H // KV // g  # of a key/value head's query heads
    q = mm(n, lp["wq"]).reshape(s, KV, parts, g, hd).transpose(1, 2, 0, 3, 4)
    k = mm(n, lp["wk"]).reshape(s, KV, hd).transpose(1, 0, 2)
    v = mm(n, lp["wv"]).reshape(s, KV, hd).transpose(1, 0, 2)
    out = lax.map(
        jax.checkpoint(partial(_attend_group, rounding=rounding)),
        (q.reshape(KV * parts, s, g, hd), jnp.repeat(k, parts, axis=0), jnp.repeat(v, parts, axis=0)),
    )  # [KV * parts, s, g, hd]
    out = out.reshape(KV, parts, s, g, hd).transpose(2, 0, 1, 3, 4)
    return mm(out.reshape(s, H * hd), lp["wo"])


def select(lp: dict, n: jax.Array, cfg: dict) -> tuple[jax.Array, jax.Array]:
    """The router on n [S, d]: (experts [S, k], weights [S, k]) over all the
    published experts, in float32 whatever the rounding."""
    s = jax.nn.sigmoid(jnp.matmul(n, lp["moe/router"].astype(jnp.float32), precision=_HIGH))
    _, chosen = lax.top_k(s + lax.stop_gradient(lp["moe/router_bias"]), sizes(cfg)["k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    return chosen, float(cfg["routed_scaling_factor"]) * picked


def _relu2(mm, n, w_up, w_down):
    return mm(jnp.square(jax.nn.relu(mm(n, w_up))), w_down)


def latent_experts(lp: dict, n: jax.Array, cfg: dict, rounding) -> tuple[jax.Array, jax.Array]:
    """The held experts' part of the routed sum, through the latent, plus the
    shared expert, on one sequence; and the selection."""
    z, mm = sizes(cfg), _mm(rounding)
    chosen, weights = select(lp, n, cfg)
    latent = mm(n, lp["latent_in"])

    def one_expert(y, expert):
        w_up, w_down, index = expert
        share = jnp.sum(jnp.where(chosen == index, weights, 0.0), axis=-1)
        return y + share[:, None] * _relu2(mm, latent, w_up, w_down), None

    held = z["first"] + jnp.arange(z["held"])
    routed, _ = lax.scan(
        jax.checkpoint(one_expert), jnp.zeros_like(latent), (lp["moe/w_up"], lp["moe/w_down"], held)
    )
    y = mm(routed, lp["latent_out"]) + _relu2(mm, n, lp["shared_up"], lp["shared_down"])
    return y, chosen


def layer(lp: dict, x: jax.Array, cfg: dict, rounding=Rounding()) -> tuple[jax.Array, jax.Array]:
    """One block on x [B, S, d], its part by the leaves it is given: (y, the
    selection [B, S, k]; zeros where the part is not the experts)."""
    eps = float(cfg["layer_norm_epsilon"])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def one_sequence(xs):
        n = _rms_norm(xs, lp["norm"], eps)
        if "moe/router" in lp:
            y, chosen = latent_experts(lp, n, cfg, rounding)
            return xs + y, chosen
        mixer = ssm_mixer if "in_proj" in lp else attention_mixer
        return xs + mixer(lp, n, cfg, rounding), jnp.zeros((xs.shape[0], sizes(cfg)["k"]), jnp.int32)

    return lax.map(jax.checkpoint(one_sequence), x)


def head_logits(norm, output, x, cfg, rounding=Rounding()):
    """[S, d] -> [S, V] of one sequence."""
    return _mm(rounding)(
        _rms_norm(x, norm, float(cfg["layer_norm_epsilon"])), output.astype(jnp.float32)
    )


def head_loss(norm, output, x, targets, cfg, rounding=Rounding()):
    """Mean next-token cross-entropy; a sequence's last position holds a
    wrapped token and is left out."""

    @jax.checkpoint
    def one_sequence(xt):
        xs, t = xt
        z = head_logits(norm, output, xs, cfg, rounding)
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
    norm, output = params["final_norm"].astype(jnp.float32), params["output"]
    out = {
        "main": jax.vmap(lambda xs: head_logits(norm, output, xs, cfg, rounding))(x),
        "loss": head_loss(norm, output, x, targets, cfg, rounding),
    }
    if selected:
        out["selected"] = jnp.stack([c.reshape(-1, sizes(cfg)["k"]) for c in selected])
    return out


def loss(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> jax.Array:
    return forward(params, tokens, targets, cfg, rounding)["loss"]


# --- the steps, block by block --------------------------------------------------


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
        table = leaf_shape("embed", cfg)
        self.embed_back = jax.jit(lambda tokens, dx: jnp.zeros(table, jnp.float32).at[tokens].add(dx))
        self.selected: list = []  # of the newest forward pass, block by block

    def gradients(self, get, tokens, targets):
        """Yield ("loss", value), then (leaf, gradient) for every leaf that
        has one: the head's two, the last block's down to the first's, and the
        table last.  `get(name)` returns the leaf's current value (a buffer's
        seeded one)."""

        def block_leaves(prefix, leaves):
            return {n: get(prefix + n) for n in leaves + _buffers(leaves)}

        x = self.embed(get("embed"), tokens)
        inputs, selected = [], []
        for prefix, leaves in self.blocks:
            inputs.append(x)
            x, chosen = self.layer(block_leaves(prefix, leaves), x)
            if _buffers(leaves):
                selected.append(chosen)
        value, (g_norm, g_output, dx) = self.head(
            get("final_norm").astype(jnp.float32), get("output").astype(jnp.float32), x, targets
        )
        self.selected = selected
        yield "loss", value
        yield "final_norm", g_norm
        yield "output", g_output
        del g_norm, g_output, x
        for prefix, leaves in reversed(self.blocks):
            grads, dx = self.layer_back(block_leaves(prefix, leaves), inputs.pop(), dx)
            for n in leaves:
                yield prefix + n, grads.pop(n)
        yield "embed", self.embed_back(tokens, dx)


@lru_cache(maxsize=4)
def _pieces(cfg_json: str, precision: str) -> _Pieces:
    """Kept so that a process that follows many seeds traces them once."""
    return _Pieces(json.loads(cfg_json), ROUNDINGS[precision])


def _decayed(name: str) -> bool:
    """What the trainer's AdamW decays: every leaf but norm scales and biases
    (`A_log` and `D` too: stacked on their run's axis they are matrices to its
    mask, which goes by a leaf's name and rank; `assumed.optimizer` says so)."""
    return not name.endswith(("norm", "bias"))


def follow(key, cfg: dict, batches, steps: int, *, precision: str = "float32",
           batch_sharding=None) -> dict:
    """Follow the first one or two AdamW steps from the seeded weights, as
    `reference/decoder.py` `follow` does; the same numbers come back, and
    `routing` where the program's selection is known."""
    if steps not in (1, 2):
        raise ValueError(f"the ssm_attn_moe reference follows 1 or 2 steps, not {steps}")
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
