"""Plain reference for the `mamba_attn` kind: a pre-norm decoder whose layers are
a mixer half and a dense SwiGLU half, the mixer a Mamba-1 selective state-space
layer or, where ``i mod attn_layer_period == attn_layer_offset``, multi-query
attention; the table tied to the head; its next-token loss, gradients and AdamW
steps, in `jax.numpy`, float32, under `jax.default_matmul_precision("highest")`.

Written from AI21-Jamba2-3B's published configuration (`jamba`), the family's
paper (arXiv:2403.19887) and Mamba's (arXiv:2312.00752, section 3 and
Algorithm 2), and imports nothing of the program; of the benchmark it takes
`reference/decoder.py`'s RMSNorm, attention of one key/value head and AdamW
steps, `reference/mla_moe.py`'s matmul and `reference/conv_attn_moe.py`'s
causal taps.  Keys are those of the published `config.json`.  d the hidden
size, I = `mamba_expand` d, N = `mamba_d_state`, R = `mamba_dt_rank`, eps
`rms_norm_eps` everywhere, no bias but where one is named:

    layer:  x = x + Mixer(RMSNorm_1(x));  x = x + SwiGLU(RMSNorm_2(x)),
            SwiGLU(h) = (silu(h W_g) * h W_u) W_d, d -> `intermediate_size` -> d
    Mamba:  [x | z] = n W_in (d -> 2 I);
            x = silu(conv(x) + b_conv), conv depthwise and causal over
            `mamba_d_conv` taps, zeros before the start;
            [delta | B | C] = x W_x (I -> R + N + N), each through an RMSNorm
            with a weight of its own (over R, N and N);
            dt = softplus(delta W_dt + b_dt) (R -> I);  A = -exp(A_log) [I, N];
            a token at a time, h in [I, N] from zero:
                h_t = exp(dt_t A) h_{t-1} + (dt_t x_t) (x) B_t
                y_t = h_t C_t + D x_t
            y = y * silu(z);  out = y W_out (I -> d); no norm after the gate
    attn:   q, k, v = n W_q, n W_k, n W_v per head of `head_dim`; no positional
            encoding; scores / sqrt(head size), causal softmax, o = concat(P v) W_o
    ends:   h0 = E[tokens];  logits = RMSNorm_f(h_L) E^T (`tie_word_embeddings`);
            loss: mean CE(t_{i+1}) over the positions that have such a token

Departures from the published model, each also under the configuration's
`assumed`: the layer order is read from `attn_layer_offset` / `attn_layer_period`
as the family's public implementation reads them; `num_experts` 1 makes every
feed-forward half dense (`expert_layer_period` / `offset` unread); the filter
is stored taps-major; `vocab_size` in the file is the rows of the table held
here (rows 0 up), and the loss is over them.

The scan is its definition, the recurrence a token at a time: a `scan` over
chunks of a rematerialised inner `scan`, so that its gradient holds a state a
chunk and a chunk's states, not a state a token.  Attention is four query heads
at a time.  `rounding` goes around every matmul (the two low-rank projections
too); the recurrence, the taps, the inner norms and the gates are elementwise
float32 on both sides and are not rounded.
"""

from __future__ import annotations

import json
import math
import zlib
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
from jax import lax

from benchmarks.precision import ROUNDINGS, Rounding
from benchmarks.reference.conv_attn_moe import short_conv
from benchmarks.reference.decoder import (
    _adam_first,
    _adam_second,
    _attend_group,
    _rms_norm,
    _sumsq,
)
from benchmarks.reference.mla_moe import _leaf_key, _mm, block_params, embed
from benchmarks.sketch import DRAWS, signs

SHARED_LEAVES = ("mixer_norm", "mlp_norm", "w_gate", "w_up", "w_down")
LAYER_LEAVES = {
    "mamba": SHARED_LEAVES + (
        "in_proj", "conv_w", "conv_bias", "x_proj", "dt_norm", "b_norm", "c_norm",
        "dt_proj", "dt_bias", "A_log", "D", "out_proj",
    ),
    "attention": SHARED_LEAVES + ("wq", "wk", "wv", "wo"),
}
TOP_LEAVES = ("embed", "final_norm")
# The head's: the final norm, and the table, whose gradient is the head's
# matmul plus the lookup's scatter in one leaf (the table is tied).
HEAD_LEAVES = ("embed", "final_norm")
# Query heads whose [S, S] scores exist at once; tokens an inner scan holds.
HEADS_AT_ONCE = 4
CHUNK = 128
_HIGH = lax.Precision.HIGHEST


def sizes(cfg: dict) -> dict:
    d = int(cfg["hidden_size"])
    return dict(
        d=d, I=int(cfg["mamba_expand"]) * d, N=int(cfg["mamba_d_state"]), R=int(cfg["mamba_dt_rank"]),
        taps=int(cfg["mamba_d_conv"]), m=int(cfg["intermediate_size"]),
        heads=int(cfg["num_attention_heads"]), KV=int(cfg["num_key_value_heads"]),
        hd=int(cfg["head_dim"]), v=int(cfg["vocab_size"]),
    )


def leaf_shape(leaf: str, cfg: dict) -> tuple[int, ...]:
    z = sizes(cfg)
    d, I, N, R = z["d"], z["I"], z["N"], z["R"]
    q, kv = z["heads"] * z["hd"], z["KV"] * z["hd"]
    return {
        "embed": (z["v"], d), "final_norm": (d,), "mixer_norm": (d,), "mlp_norm": (d,),
        "w_gate": (d, z["m"]), "w_up": (d, z["m"]), "w_down": (z["m"], d),
        "in_proj": (d, 2 * I), "conv_w": (z["taps"], I), "conv_bias": (I,),
        "x_proj": (I, R + 2 * N), "dt_norm": (R,), "b_norm": (N,), "c_norm": (N,),
        "dt_proj": (R, I), "dt_bias": (I,), "A_log": (I, N), "D": (I,), "out_proj": (I, d),
        "wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
    }[leaf]


def kinds(cfg: dict) -> list[str]:
    """The kind of each held layer: the published layers 0 up."""
    period, offset = int(cfg["attn_layer_period"]), int(cfg["attn_layer_offset"])
    return ["attention" if i % period == offset else "mamba" for i in range(int(cfg["num_hidden_layers"]))]


def layers(cfg: dict) -> list[tuple[str, tuple[str, ...]]]:
    """(prefix, leaves) of every layer in forward order."""
    return [(f"layers/{i}/", LAYER_LEAVES[k]) for i, k in enumerate(kinds(cfg))]


def all_leaves(cfg: dict) -> list[str]:
    return list(TOP_LEAVES) + [prefix + n for prefix, leaves in layers(cfg) for n in leaves]


def leaf_kind(name: str) -> str:
    """`layers/2/in_proj` -> `in_proj`."""
    return name.rsplit("/", 1)[-1]


def init_leaf(key: jax.Array, name: str, cfg: dict) -> jax.Array:
    """One leaf from the seed, in the type it is stored in: matrices
    n / sqrt(fan_in) in the configuration's dtype (the table's fan-in is its
    row, the filter's its taps; the filter float32, as the model keeps it: its
    elements are of order 0.5, where bfloat16's spacing is several AdamW
    steps), norm scales 1 + 0.1 n in float32.

    The state-space leaves get the decays a trained model has, so that a state
    carried across the chunks is what the check reads: `A_log[c, n]` = log(n + 1)
    (Mamba's S4D-real); `dt_bias` the softplus' inverse of dt log-uniform in
    [`time_step_min`, `time_step_max`] (Mamba's defaults; the published config
    does not carry them); `D` 1; the convolution's bias 0.1 n; all float32.

    The mixers' output projections (`out_proj`, `wo`) are 0.03 n / sqrt(fan_in),
    as the sibling references draw theirs and for their reason: a causal
    mixer's output on seeded weights is nearly the same vector for every token
    and several times an embedding row."""
    return _draw(_leaf_key(key, name), leaf_kind(name), cfg)


def _draw(key: jax.Array, leaf: str, cfg: dict) -> jax.Array:
    shape = leaf_shape(leaf, cfg)
    if leaf == "A_log":
        return jnp.log(jnp.broadcast_to(jnp.arange(1, shape[1] + 1, dtype=jnp.float32), shape))
    if leaf == "dt_bias":
        low, high = math.log(float(cfg["time_step_min"])), math.log(float(cfg["time_step_max"]))
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32, low, high))
        return dt + jnp.log(-jnp.expm1(-dt))
    if leaf == "D":
        return jnp.ones(shape, jnp.float32)
    n = jax.random.normal(key, shape, jnp.float32)
    if leaf.endswith("norm"):
        return 1.0 + 0.1 * n
    if leaf == "conv_bias":
        return 0.1 * n
    fan_in = {"embed": shape[1], "conv_w": shape[0]}.get(leaf, shape[-2])
    scale = 0.03 if leaf in ("out_proj", "wo") else 1.0
    dtype = jnp.float32 if leaf == "conv_w" else jnp.dtype(cfg["torch_dtype"])
    return (scale * n / math.sqrt(fan_in)).astype(dtype)


def init_params(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    return {name: init_leaf(key, name, cfg) for name in all_leaves(cfg)}


# --- forward ----------------------------------------------------------------


def recurrence(x, dt, A, B, C, D) -> jax.Array:
    """The selective scan as it is defined, on one sequence: x and dt [S, I],
    A [I, N], B and C [S, N], D [I] -> y [S, I], the state h [I, N] stepping a
    token at a time.  The tokens go `CHUNK` at a time through a rematerialised
    inner `scan`; a tail is padded with dt = 0, which leaves the state as it is."""
    S, I = x.shape
    pad = -S % CHUNK

    def chunks(a):
        a = jnp.pad(a, ((0, pad), (0, 0)))
        return a.reshape((S + pad) // CHUNK, CHUNK, a.shape[1])

    def token(h, t):
        xt, dtt, Bt, Ct = t
        h = jnp.exp(dtt[:, None] * A) * h + (dtt * xt)[:, None] * Bt[None, :]
        return h, jnp.sum(h * Ct[None, :], axis=1) + D * xt

    @jax.checkpoint
    def one_chunk(h, c):
        return lax.scan(token, h, c)

    _, y = lax.scan(one_chunk, jnp.zeros(A.shape, jnp.float32), tuple(chunks(a) for a in (x, dt, B, C)))
    return y.reshape(S + pad, I)[:S]


def mamba_mixer(lp: dict, n: jax.Array, cfg: dict, rounding) -> jax.Array:
    """Mamba-1 on one sequence's normalised input n [S, d]."""
    z, mm = sizes(cfg), _mm(rounding)
    eps = float(cfg["rms_norm_eps"])
    x, gate = jnp.split(mm(n, lp["in_proj"]), 2, axis=-1)
    x = jax.nn.silu(short_conv(x, lp["conv_w"]) + lp["conv_bias"])
    delta, B, C = jnp.split(mm(x, lp["x_proj"]), (z["R"], z["R"] + z["N"]), axis=-1)
    delta = _rms_norm(delta, lp["dt_norm"], eps)
    B, C = _rms_norm(B, lp["b_norm"], eps), _rms_norm(C, lp["c_norm"], eps)
    dt = jax.nn.softplus(mm(delta, lp["dt_proj"]) + lp["dt_bias"])
    y = recurrence(x, dt, -jnp.exp(lp["A_log"]), B, C, lp["D"])
    return mm(y * jax.nn.silu(gate), lp["out_proj"])


def attention_mixer(lp: dict, n: jax.Array, cfg: dict, rounding) -> jax.Array:
    """Causal attention without positions on one sequence n [S, d],
    `HEADS_AT_ONCE` query heads of one key/value head at a time, each
    recomputed in the backward pass."""
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


def layer(lp: dict, x: jax.Array, cfg: dict, rounding=Rounding()) -> jax.Array:
    """One layer on x [B, S, d], its mixer by the leaves it is given."""
    eps, mm = float(cfg["rms_norm_eps"]), _mm(rounding)
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
    mixer = mamba_mixer if "in_proj" in lp else attention_mixer

    def one_sequence(xs):
        xs = xs + mixer(lp, _rms_norm(xs, lp["mixer_norm"], eps), cfg, rounding)
        n = _rms_norm(xs, lp["mlp_norm"], eps)
        return xs + mm(jax.nn.silu(mm(n, lp["w_gate"])) * mm(n, lp["w_up"]), lp["w_down"])

    return lax.map(jax.checkpoint(one_sequence), x)


def head_logits(norm, table, x, cfg, rounding=Rounding()):
    """[S, d] -> [S, V] of one sequence, through the tied table."""
    return _mm(rounding)(_rms_norm(x, norm, float(cfg["rms_norm_eps"])), table.astype(jnp.float32).T)


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
    the logits and the loss."""
    x = embed(params["embed"], tokens)
    for prefix, _ in layers(cfg):
        x = layer(block_params(params, prefix), x, cfg, rounding)
    norm, table = params["final_norm"].astype(jnp.float32), params["embed"]
    return {
        "main": jax.vmap(lambda xs: head_logits(norm, table, xs, cfg, rounding))(x),
        "loss": head_loss(norm, table, x, targets, cfg, rounding),
    }


def loss(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> jax.Array:
    return forward(params, tokens, targets, cfg, rounding)["loss"]


# --- the steps, layer by layer --------------------------------------------------


@jax.jit
def _salted_sketch(x, name_crc, key):
    """`benchmarks.sketch.sketch` with the leaf's name as a number: one program
    a shape and not one a name (235 leaves here, 23 shapes)."""
    data = jax.random.key_data(key).astype(jnp.uint32).reshape(-1)
    salt = data[0] ^ (data[-1] * jnp.uint32(0x9E3779B1)) ^ name_crc
    x = x.astype(jnp.float32)
    return jnp.stack([
        jnp.sum(x * signs(x.shape, salt + jnp.uint32(0x632BE5AB * (draw + 1) % 2**32)))
        for draw in range(DRAWS)
    ])


def _sketch(x, name: str, key):
    return _salted_sketch(x, jnp.uint32(zlib.crc32(name.encode())), key)



class _Pieces:
    """The jitted parts one configuration and precision need."""

    def __init__(self, cfg: dict, rounding):
        self.cfg = cfg
        self.layers = layers(cfg)
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
        draw = jax.jit(partial(_draw, cfg=cfg), static_argnums=1)  # one program a kind of leaf
        self.fresh = lambda key, name: draw(_leaf_key(key, name), leaf_kind(name))
        # The table's gradient: what the head gave it plus the lookup's scatter.
        self.embed_back = jax.jit(lambda head_part, tokens, dx: head_part.at[tokens].add(dx))

    def gradients(self, get, tokens, targets):
        """Yield ("loss", value), then (leaf, gradient) for every leaf: the
        final norm's, the last layer's down to the first's, and the tied
        table's last, the sum of its two uses.  `get(name)` returns the leaf's
        current value."""
        x = self.embed(get("embed"), tokens)
        inputs = []
        for prefix, leaves in self.layers:
            inputs.append(x)
            x = self.layer({n: get(prefix + n) for n in leaves}, x)
        value, (g_norm, g_table, dx) = self.head(
            get("final_norm").astype(jnp.float32), get("embed").astype(jnp.float32), x, targets
        )
        yield "loss", value
        yield "final_norm", g_norm
        del g_norm, x
        for prefix, leaves in reversed(self.layers):
            grads, dx = self.layer_back({n: get(prefix + n) for n in leaves}, inputs.pop(), dx)
            for n in leaves:
                yield prefix + n, grads.pop(n)
        yield "embed", self.embed_back(g_table, tokens, dx)


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
    `reference/decoder.py` `follow` does; the same numbers come back."""
    if steps not in (1, 2):
        raise ValueError(f"the mamba_attn reference follows 1 or 2 steps, not {steps}")
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
        p1 = {}
        grads = pieces.gradients(seeded, tokens, targets)
        next(grads)
        for name, g in grads:
            p1[name] = _adam_first(seeded(name), g, clip1, lr, eps, wd, decay=_decayed(name))
        if steps == 1:
            out["update_norm"] = {
                name: math.sqrt(float(_sumsq(p - seeded(name).astype(jnp.float32))))
                for name, p in p1.items()
            }
        if steps == 2:
            tokens2, targets2 = (place(a) for a in batches[1])
            loss2, _, clip2, _ = norm_pass(p1.__getitem__, tokens2, targets2)
            out["loss"].append(loss2)
            first = pieces.gradients(seeded, tokens, targets)
            second = pieces.gradients(p1.__getitem__, tokens2, targets2)
            next(first), next(second)
            moved = {}
            for (name, g1), (_, g2) in zip(first, second):
                moved[name] = _adam_second(
                    seeded(name), p1[name], g1, g2, clip1, clip2,
                    lr, b1, b2, eps, wd, decay=_decayed(name),
                )
            out["update_norm"] = {k: math.sqrt(float(v)) for k, v in moved.items()}
    return out
