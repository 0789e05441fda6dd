"""Plain reference for the `mla_moe` kind: a pre-norm decoder whose blocks
are multi-head latent attention and a dense or routed feed-forward, with a
multi-token-prediction module, its two-term loss, gradients and AdamW steps,
in `jax.numpy`, float32, under `jax.default_matmul_precision("highest")`.

Written from the DeepSeek-V3 report (arXiv:2412.19437, sections 2.1 and 2.2),
whose block `glm4_moe_lite` follows key for key, and imports nothing of the
program; of the benchmark it takes `reference/decoder.py`'s RMSNorm, rotary
embedding, head-by-head attention and AdamW steps.  Keys are those of the
published `config.json`.  d the hidden size, H heads, no biases:

    block:  h = x + MLA(RMSNorm(x));  y = h + FFN(RMSNorm(h))
    MLA:    c_q = RMSNorm(x W_qa);  q = c_q W_qb -> per head q_nope | q_rope
            [c_kv | k_r] = x W_kva;  kv = RMSNorm(c_kv) W_kvb -> per head k_nope | v
            q_rope and the one k_r (shared by all heads) rotated, split halves;
            q = [q_nope | q_rope], k = [k_nope | k_r]; scores / sqrt(qk size),
            causal softmax, o = concat(P v) W_o
    FFN:    the first `first_k_dense_replace` blocks a SwiGLU of width
            `intermediate_size`; every other block
            s = sigmoid(x W_r) over all experts; the top k by s + b (b a buffer
            without gradient); w_i = scale * s_i / (sum of the selected s + 1e-20);
            y = sum over the selected experts *held here* of w_i E_i(x), plus the
            shared expert E_s(x); each E a SwiGLU of width `moe_intermediate_size`
    MTP:    h' = [RMSNorm(h_L) | RMSNorm(Emb(t_{i+1}))] W_eh, one routed block,
            its own RMSNorm, the main head: predicts t_{i+2}
    loss:   mean CE(t_{i+1}) + `mtp_loss_weight` * mean CE(t_{i+2}), each over the positions
            that have such a token

The routed sum is a plain loop over the held experts, each applied to every
token under a mask: no sorting, no gather.  `n_routed_experts` in the file is
the number held here, `published.n_routed_experts` the router's width, and
`deployment.rank` says which span: experts rank * held .. (rank + 1) * held - 1.
What the absent experts would add is left out, as in the program.

The steps are followed layer by layer as `reference/decoder.py` does and for
its reasons.  `rounding` goes around every matmul but the router's, which is
float32 in the model itself: a score rounded lower selects other experts.
`follow` also counts the assignments on which the program's selection at the
seeded weights differs from this one's, where a builder has given it the
program's (`program_routing`), prints the count and returns it under `routing`.
"""

from __future__ import annotations

import json
import math
import sys
import zlib
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

ATTENTION_LEAVES = (
    "attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo", "mlp_norm",
)
DENSE_LEAVES = ATTENTION_LEAVES + ("w_gate", "w_up", "w_down")
ROUTED_LEAVES = ATTENTION_LEAVES + (
    "moe/router", "moe/w_gate", "moe/w_up", "moe/w_down",
    "moe/shared_gate", "moe/shared_up", "moe/shared_down",
)
# Held at its seeded value: no gradient, no update, not compared.
BUFFERS = ("moe/router_bias",)
MTP_LEAVES = ("mtp/hidden_norm", "mtp/embed_norm", "mtp/join", "mtp/final_norm")
TOP_LEAVES = ("embed", "output", "final_norm")
# Their gradient is the forward pass's result times the loss's derivative.
HEAD_LEAVES = ("output", "final_norm", "mtp/final_norm")
_HIGH = lax.Precision.HIGHEST

# A builder may set this to `f(key, tokens, targets) -> [blocks, B * S, k]`,
# the experts the program selects at the seeded weights.
program_routing = None
# What `follow` last counted with it (`differing_assignments`), for the notes.
last_routing = None


def sizes(cfg: dict) -> dict:
    held = int(cfg["n_routed_experts"])
    return dict(
        d=int(cfg["hidden_size"]), H=int(cfg["num_attention_heads"]),
        q_rank=int(cfg["q_lora_rank"]), kv_rank=int(cfg["kv_lora_rank"]),
        nope=int(cfg["qk_nope_head_dim"]), rope=int(cfg["qk_rope_head_dim"]),
        vd=int(cfg["v_head_dim"]), f=int(cfg["intermediate_size"]),
        m=int(cfg["moe_intermediate_size"]), v=int(cfg["vocab_size"]),
        held=held, routed=int(cfg["published"]["n_routed_experts"]),
        first=int(cfg["deployment"]["rank"]) * held, k=int(cfg["num_experts_per_tok"]),
        shared=int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        dense_layers=int(cfg["first_k_dense_replace"]),
        layers=int(cfg["num_hidden_layers"]),
        predict=int(cfg["num_nextn_predict_layers"]),
    )


def leaf_shape(leaf: str, cfg: dict) -> tuple[int, ...]:
    z = sizes(cfg)
    d, H = z["d"], z["H"]
    return {
        "embed": (z["v"], d), "output": (d, z["v"]), "final_norm": (d,),
        "attn_norm": (d,), "mlp_norm": (d,), "q_norm": (z["q_rank"],), "kv_norm": (z["kv_rank"],),
        "wq_a": (d, z["q_rank"]), "wq_b": (z["q_rank"], H * (z["nope"] + z["rope"])),
        "wkv_a": (d, z["kv_rank"] + z["rope"]),
        "wkv_b": (z["kv_rank"], H * (z["nope"] + z["vd"])), "wo": (H * z["vd"], d),
        "w_gate": (d, z["f"]), "w_up": (d, z["f"]), "w_down": (z["f"], d),
        "moe/router": (d, z["routed"]), "moe/router_bias": (z["routed"],),
        "moe/w_gate": (z["held"], d, z["m"]), "moe/w_up": (z["held"], d, z["m"]),
        "moe/w_down": (z["held"], z["m"], d),
        "moe/shared_gate": (d, z["shared"]), "moe/shared_up": (d, z["shared"]),
        "moe/shared_down": (z["shared"], d),
        "mtp/hidden_norm": (d,), "mtp/embed_norm": (d,), "mtp/join": (2 * d, d),
        "mtp/final_norm": (d,),
    }[leaf]


def blocks(cfg: dict) -> list[tuple[str, tuple[str, ...]]]:
    """(prefix, leaves) of every block in forward order, the prediction
    module's last."""
    z = sizes(cfg)
    rows = [(f"dense/{i}/", DENSE_LEAVES) for i in range(z["dense_layers"])]
    rows += [(f"layers/{i}/", ROUTED_LEAVES) for i in range(z["layers"] - z["dense_layers"])]
    if z["predict"]:
        rows.append(("mtp/block/", ROUTED_LEAVES))
    return rows


def all_leaves(cfg: dict, buffers: bool = False) -> list[str]:
    names = list(TOP_LEAVES) + (list(MTP_LEAVES) if sizes(cfg)["predict"] else [])
    for prefix, leaves in blocks(cfg):
        names += [prefix + n for n in leaves]
        if buffers and leaves is ROUTED_LEAVES:
            names += [prefix + n for n in BUFFERS]
    return names


def leaf_kind(name: str) -> str:
    """`layers/3/moe/w_gate` -> `moe/w_gate`, `dense/0/wo` -> `wo`."""
    if name in TOP_LEAVES + MTP_LEAVES:
        return name
    tail = name.rsplit("/", 1)[-1]
    return "moe/" + tail if "/moe/" in name else tail


def init_leaf(key: jax.Array, name: str, cfg: dict) -> jax.Array:
    """One leaf from the seed, in the type it is stored in: matrices
    n / sqrt(fan_in) in the configuration's dtype (an expert stack's fan-in is
    its middle axis; the embedding's its row), the router the same in float32,
    norm scales 1 + 0.1 n in float32.

    Two leaves are drawn smaller, for the router's sake.  Causal attention
    with seeded weights gives every token nearly the same vector, the running
    mean of the values before it, and against embedding rows of 1 / sqrt(d)
    (larger ones are beyond what a bfloat16 weight can take an AdamW step of
    3e-4 on) that common part decides the router's choice for all tokens
    alike.  With `wo` at n / sqrt(fan_in) and a selection bias of 0.1 n one
    expert got 72% of a layer's tokens at the cell's size, and the 16 held
    experts between 6% and 52% of a layer's assignments from seed to seed (my
    chip runs, PR 26, scripts/chip_routing_balance.py): a step's time then
    follows the seed.  A trained router does not do that: its bias exists to
    balance it.  So `wo`, the attention's output projection, is
    0.03 n / sqrt(fan_in) and the selection bias 0.01 n: the held experts then
    get 24.8-25.7% of the assignments over a model's blocks on every seed, and
    the most loaded held expert of a block 1.7-4.0 times the mean."""
    return _draw(_leaf_key(key, name), leaf_kind(name), cfg)


def _leaf_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) % 2**31)


def _draw(key: jax.Array, leaf: str, cfg: dict) -> jax.Array:
    shape = leaf_shape(leaf, cfg)
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


def _mm(rounding):
    def mm(a, w):
        return rounding.result(
            jnp.matmul(rounding.operand(a), rounding.operand(w), precision=_HIGH)
        )

    return mm


def _swiglu(mm, n, w_gate, w_up, w_down):
    return mm(jax.nn.silu(mm(n, w_gate)) * mm(n, w_up), w_down)


def latent_attention(lp: dict, n: jax.Array, cfg: dict, rounding) -> jax.Array:
    """MLA on one sequence's normalised input n [S, d]."""
    z, mm = sizes(cfg), _mm(rounding)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    s, H, nope, rope, vd = n.shape[0], z["H"], z["nope"], z["rope"], z["vd"]
    if nope + rope != vd:
        raise ValueError("decoder._attention takes one head size for q, k and v")
    q = mm(_rms_norm(mm(n, lp["wq_a"]), lp["q_norm"], eps), lp["wq_b"]).reshape(s, H, nope + rope)
    down = mm(n, lp["wkv_a"])
    c, k_r = down[:, : z["kv_rank"]], down[:, z["kv_rank"] :]
    kv = mm(_rms_norm(c, lp["kv_norm"], eps), lp["wkv_b"]).reshape(s, H, nope + vd)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], axis=-1)
    k_r = jnp.broadcast_to(_rope(k_r[:, None, :], theta), (s, H, rope))
    k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    return mm(_attention(q, k, kv[..., nope:], rounding), lp["wo"])


def select(lp: dict, n: jax.Array, cfg: dict) -> tuple[jax.Array, jax.Array]:
    """The router on n [S, d]: (experts [S, k], weights [S, k]) over all the
    published experts, in float32 whatever the rounding."""
    z = sizes(cfg)
    s = jax.nn.sigmoid(jnp.matmul(n, lp["moe/router"].astype(jnp.float32), precision=_HIGH))
    _, chosen = lax.top_k(s + lax.stop_gradient(lp["moe/router_bias"]), z["k"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    scale = float(cfg["routed_scaling_factor"])
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return chosen, scale * picked


def routed_ffn(lp: dict, n: jax.Array, cfg: dict, rounding) -> tuple[jax.Array, jax.Array]:
    """sum over the selected held experts of w_i E_i(n) + E_shared(n) on one
    sequence, and the selection."""
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


def layer(lp: dict, x: jax.Array, cfg: dict, rounding=Rounding()) -> tuple[jax.Array, jax.Array]:
    """One block on x [B, S, d], dense or routed by the leaves it is given:
    (y, the selection [B, S, k]; zeros for a dense block)."""
    eps = float(cfg["rms_norm_eps"])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def one_sequence(xs):
        xs = xs + latent_attention(lp, _rms_norm(xs, lp["attn_norm"], eps), cfg, rounding)
        n = _rms_norm(xs, lp["mlp_norm"], eps)
        if "moe/router" in lp:
            y, chosen = routed_ffn(lp, n, cfg, rounding)
            return xs + y, chosen
        y = _swiglu(_mm(rounding), n, lp["w_gate"], lp["w_up"], lp["w_down"])
        return xs + y, jnp.zeros((xs.shape[0], sizes(cfg)["k"]), jnp.int32)

    return lax.map(jax.checkpoint(one_sequence), x)


def embed(table: jax.Array, tokens: jax.Array) -> jax.Array:
    return table.astype(jnp.float32)[tokens]


def head_logits(norm, output, x, cfg, rounding=Rounding()):
    """[S, d] -> [S, V] of one sequence."""
    return rounding.result(jnp.matmul(
        rounding.operand(_rms_norm(x, norm, float(cfg["rms_norm_eps"]))),
        rounding.operand(output.astype(jnp.float32)), precision=_HIGH,
    ))


def head_loss(norm, output, x, targets, cfg, rounding=Rounding(), ahead: int = 1):
    """Mean cross-entropy of `targets`; a sequence's last `ahead` positions
    hold a wrapped token and are left out."""

    @jax.checkpoint
    def one_sequence(xt):
        xs, t = xt
        z = head_logits(norm, output, xs, cfg, rounding)
        nll = jax.nn.logsumexp(z, axis=-1) - jnp.take_along_axis(z, t[:, None], axis=-1)[:, 0]
        return jnp.sum(nll[: nll.shape[0] - ahead])

    b, s = targets.shape
    return jnp.sum(lax.map(one_sequence, (x, targets))) / (b * (s - ahead))


def join(mtp: dict, x: jax.Array, next_embedding: jax.Array, cfg: dict, rounding=Rounding()):
    """h' = [RMSNorm(h) | RMSNorm(Emb(t_{i+1}))] W_eh."""
    eps = float(cfg["rms_norm_eps"])
    both = jnp.concatenate(
        [
            _rms_norm(x, mtp["mtp/hidden_norm"].astype(jnp.float32), eps),
            _rms_norm(next_embedding, mtp["mtp/embed_norm"].astype(jnp.float32), eps),
        ],
        axis=-1,
    )
    return _mm(rounding)(both, mtp["mtp/join"].astype(jnp.float32))


def mtp_hidden(mtp, block, x, next_embedding, cfg, rounding=Rounding()):
    """The prediction module up to its own final norm's input, and its
    block's selection."""
    return layer(block, join(mtp, x, next_embedding, cfg, rounding), cfg, rounding)


def mtp_loss(mtp, block, output, x, next_embedding, targets, cfg, rounding=Rounding()):
    """The prediction module's loss (unweighted) and its block's selection."""
    h, chosen = mtp_hidden(mtp, block, x, next_embedding, cfg, rounding)
    value = head_loss(
        mtp["mtp/final_norm"].astype(jnp.float32), output.astype(jnp.float32), h,
        jnp.roll(targets, -1, axis=1), cfg, rounding, ahead=2,
    )
    return value, chosen


def block_params(params: dict, prefix: str) -> dict:
    """A block's leaves, buffers too, without the prefix."""
    return {k[len(prefix):]: v for k, v in params.items() if k.startswith(prefix)}


def forward(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> dict:
    """The whole forward pass at once, for sizes where that fits (tests):
    both heads' logits, the loss and every routed block's selection."""
    z = sizes(cfg)
    x = embed(params["embed"], tokens)
    selected = []
    for prefix, leaves in blocks(cfg):
        if prefix == "mtp/block/":
            continue
        x, chosen = layer(block_params(params, prefix), x, cfg, rounding)
        if leaves is ROUTED_LEAVES:
            selected.append(chosen)
    norm, output = params["final_norm"].astype(jnp.float32), params["output"]
    out = {"main": jax.vmap(lambda xs: head_logits(norm, output, xs, cfg, rounding))(x)}
    out["loss"] = head_loss(norm, output.astype(jnp.float32), x, targets, cfg, rounding)
    if z["predict"]:
        mtp = {n: params[n] for n in MTP_LEAVES}
        h, chosen = mtp_hidden(
            mtp, block_params(params, "mtp/block/"), x, embed(params["embed"], targets), cfg,
            rounding,
        )
        selected.append(chosen)
        m_norm = mtp["mtp/final_norm"].astype(jnp.float32)
        out["mtp"] = jax.vmap(lambda xs: head_logits(m_norm, output, xs, cfg, rounding))(h)
        out["mtp_loss"] = head_loss(
            m_norm, output.astype(jnp.float32), h, jnp.roll(targets, -1, axis=1), cfg, rounding,
            ahead=2,
        )
        out["loss"] = out["loss"] + float(cfg["mtp_loss_weight"]) * out["mtp_loss"]
    out["selected"] = jnp.stack([c.reshape(-1, z["k"]) for c in selected])
    return out


def loss(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> jax.Array:
    return forward(params, tokens, targets, cfg, rounding)["loss"]


# --- the steps, layer by layer ------------------------------------------------


class _Pieces:
    """The jitted parts one configuration and precision need."""

    def __init__(self, cfg: dict, rounding):
        self.cfg = cfg
        self.blocks = [b for b in blocks(cfg) if b[0] != "mtp/block/"]
        self.predict = bool(sizes(cfg)["predict"])
        self.embed = jax.jit(embed)
        self.layer = jax.jit(partial(layer, cfg=cfg, rounding=rounding))
        self.head = jax.jit(
            jax.value_and_grad(partial(head_loss, cfg=cfg, rounding=rounding), argnums=(0, 1, 2))
        )
        self.mtp = jax.jit(jax.value_and_grad(
            partial(mtp_loss, cfg=cfg, rounding=rounding), argnums=(0, 1, 2, 3, 4), has_aux=True
        ))

        def layer_back(lp, x, dy):
            lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
            _, pull, _ = jax.vjp(partial(layer, cfg=cfg, rounding=rounding), lp, x, has_aux=True)
            return pull(dy)

        self.layer_back = jax.jit(layer_back)
        draw = jax.jit(partial(_draw, cfg=cfg), static_argnums=1)  # one program a kind of leaf
        self.fresh = lambda key, name: draw(_leaf_key(key, name), leaf_kind(name))
        self.embed_back = jax.jit(
            lambda tokens, dx, rows: jnp.zeros((rows, dx.shape[-1]), jnp.float32).at[tokens].add(dx),
            static_argnums=2,
        )
        self.selected: list = []  # of the newest forward pass, block by block

    def gradients(self, get, tokens, targets):
        """Yield ("loss", value), then (leaf, gradient) for every leaf that
        has one, the heads first and the first block last.  `get(name)`
        returns the leaf's current value (a buffer's seeded one)."""

        def block_leaves(prefix, leaves):
            buffers = BUFFERS if leaves is ROUTED_LEAVES else ()
            return {n: get(prefix + n) for n in leaves + buffers}

        x = self.embed(get("embed"), tokens)
        inputs, selected = [], []
        for prefix, leaves in self.blocks:
            inputs.append(x)
            x, chosen = self.layer(block_leaves(prefix, leaves), x)
            if leaves is ROUTED_LEAVES:
                selected.append(chosen)
        final_norm = get("final_norm").astype(jnp.float32)
        output = get("output").astype(jnp.float32)
        value, (g_norm, g_out, dx) = self.head(final_norm, output, x, targets)
        d_next = None
        if self.predict:
            mtp = {n: get(n).astype(jnp.float32) for n in MTP_LEAVES}
            block = {
                k: v.astype(jnp.float32)
                for k, v in block_leaves("mtp/block/", ROUTED_LEAVES).items()
            }
            (extra, chosen), (g_mtp, g_block, g_out2, dx2, d_next) = self.mtp(
                mtp, block, output, x, self.embed(get("embed"), targets), targets
            )
            selected.append(chosen)
            w = float(self.cfg["mtp_loss_weight"])
            value, g_out, dx = value + w * extra, g_out + w * g_out2, dx + w * dx2
        self.selected = selected
        yield "loss", value
        yield "final_norm", g_norm
        yield "output", g_out
        del g_norm, g_out, x, output
        if self.predict:
            for n in MTP_LEAVES:
                yield n, w * g_mtp.pop(n)
            for n in ROUTED_LEAVES:
                yield "mtp/block/" + n, w * g_block.pop(n)
            del g_block, dx2
        for prefix, leaves in reversed(self.blocks):
            grads, dx = self.layer_back(block_leaves(prefix, leaves), inputs.pop(), dx)
            for n in leaves:
                yield prefix + n, grads.pop(n)
        rows = sizes(self.cfg)["v"]
        g_embed = self.embed_back(tokens, dx, rows)
        if d_next is not None:
            g_embed = g_embed + self.embed_back(targets, w * d_next, rows)
        yield "embed", g_embed


@lru_cache(maxsize=4)
def _pieces(cfg_json: str, precision: str) -> _Pieces:
    """Kept so that a process that follows many seeds traces them once."""
    return _Pieces(json.loads(cfg_json), ROUNDINGS[precision])


def _decayed(name: str) -> bool:
    return not name.endswith("norm")


def differing_assignments(ours: np.ndarray, theirs: np.ndarray) -> dict:
    """Two selections [blocks, tokens, k]: how many of a token's k experts
    the other side did not choose, summed."""
    same = (ours[..., :, None] == theirs[..., None, :]).any(axis=-1).sum()
    total = int(ours.size)
    return {"assignments": total, "differing": total - int(same),
            "share": (total - int(same)) / total}


def follow(key, cfg: dict, batches, steps: int, *, precision: str = "float32",
           batch_sharding=None) -> dict:
    """Follow the first one or two AdamW steps from the seeded weights, as
    `reference/decoder.py` `follow` does; the same numbers come back, and
    `routing` where the program's selection is known."""
    if steps not in (1, 2):
        raise ValueError(f"the mla_moe reference follows 1 or 2 steps, not {steps}")
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
            "head_leaves": list(HEAD_LEAVES) if pieces.predict else list(HEAD_LEAVES[:2]),
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
            ours, np.asarray(program_routing(key, tokens, targets))
        )
        print(json.dumps({"routing": out["routing"]}), file=sys.stderr, flush=True)
    return out
