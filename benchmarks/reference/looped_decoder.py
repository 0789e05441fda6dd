"""Plain reference for the `looped_decoder` kind: a decoder-only transformer
whose one stack of blocks is applied `total_ut_steps` times a step on the same
weights (Ouro, "Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741), its objective, gradients and AdamW steps, in `jax.numpy`,
float32, under `jax.default_matmul_precision("highest")`.  It imports nothing
of the program; the parts it shares with the `decoder` kind's reference
(RMSNorm, rotary positions, dense causal attention a head at a time, AdamW's
two steps in closed form) are that file's.

- **A block** (sandwich normalisation): a = Attn(RMSNorm_1(x)),
  x <- x + RMSNorm_2(a); m = SwiGLU(RMSNorm_3(x)), x <- x + RMSNorm_4(m).
  Attn: q, k, v projections without bias, rotary on q and k over the whole
  head, causal softmax(q k^T / sqrt(head_dim)) v, the output projection.
- **The loop.**  h = E[tokens]; for t = 1..T: h through the L blocks in turn,
  the same leaves every pass; n_t = RMSNorm_f(h); the next pass starts from
  n_t; logits_t = n_t W_out; l_t(i) = -log softmax(logits_t(i))[target(i)].
- **The exit gate.**  z_t(i) = n_t(i) . w_g + b_g, lambda_t = sigmoid(z_t) for
  t < T; S_0 = 1, S_t = S_{t-1} (1 - lambda_t); p_t = lambda_t S_{t-1} for
  t < T and p_T = S_{T-1}.
- **The objective**, over the N positions that have a target:
  (1/N) sum_i [ sum_t p_t(i) l_t(i) - beta H(p(i)) ], H = -sum_t p_t log p_t.

The gradients are followed a block application at a time from stored
pass-and-layer inputs, the last pass first; a layer's leaves are summed over
its T applications in float32, so every leaf's gradient is whole only when the
backward pass has ended and `gradients` hands them out together.  The head
and the losses are computed in blocks of `HEAD_ROWS` rows, so that a block's
float32 logits and not a sequence's are alive.  `follow` therefore keeps the
first gradient (the second step's moments need it) and computes each step's
once.

`rounding` goes around every matmul the program computes in the
configuration's type (`benchmarks/precision.py`): the identity gives the
reference, fp8 the lower-precision control.  The gate is float32 on both
sides, as the configuration states it.
"""

from __future__ import annotations

import json
import math
from functools import lru_cache, partial

import jax
import jax.numpy as jnp
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
    embed,
    leaf_name,
)

LAYER_LEAVES = (
    "attn_norm", "wq", "wk", "wv", "wo", "attn_post_norm",
    "mlp_norm", "w_gate", "w_up", "w_down", "mlp_post_norm",
)
TOP_LEAVES = ("embed", "output", "final_norm", "exit_gate_w", "exit_gate_b")
# The leaves of a pass's head unit: what every pass's loss reads directly.
HEAD_LEAVES = TOP_LEAVES[1:]
HEAD_ROWS = 1024
_HIGH = lax.Precision.HIGHEST
_FLOAT32_LEAVES = ("exit_gate_w", "exit_gate_b")


def leaf_shape(name: str, cfg: dict) -> tuple[int, ...]:
    d, hd = int(cfg["hidden_size"]), int(cfg["head_dim"])
    h, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    f, v = int(cfg["intermediate_size"]), int(cfg["vocab_size"])
    return {
        "embed": (v, d), "output": (d, v), "final_norm": (d,),
        "exit_gate_w": (d,), "exit_gate_b": (),
        "attn_norm": (d,), "attn_post_norm": (d,), "mlp_norm": (d,), "mlp_post_norm": (d,),
        "wq": (d, h * hd), "wk": (d, kv * hd), "wv": (d, kv * hd),
        "wo": (h * hd, d), "w_gate": (d, f), "w_up": (d, f), "w_down": (f, d),
    }[name]


def all_leaves(cfg: dict) -> list[tuple[str, int | None]]:
    rows: list[tuple[str, int | None]] = [(n, None) for n in TOP_LEAVES]
    for i in range(int(cfg["num_hidden_layers"])):
        rows += [(n, i) for n in LAYER_LEAVES]
    return rows


def init_leaf(key: jax.Array, name: str, layer: int | None, cfg: dict) -> jax.Array:
    """One leaf from the seed, in the type it is stored in: matrices
    n / sqrt(fan_in) in the configuration's dtype as the `decoder` kind's,
    every norm's scale 1 in float32, the gate's vector at the head's scale and
    its bias 0, both float32: lambda is then near a half, every pass's loss
    carries weight and the gate's gradient is not negligible."""
    shape = leaf_shape(name, cfg)
    if name.endswith("norm"):
        return jnp.ones(shape, jnp.float32)
    if name == "exit_gate_b":
        return jnp.zeros(shape, jnp.float32)
    index = (TOP_LEAVES + LAYER_LEAVES).index(name)
    k = jax.random.fold_in(jax.random.fold_in(key, index), 0 if layer is None else layer + 1)
    n = jax.random.normal(k, shape, jnp.float32)
    n = n / math.sqrt(shape[1] if name == "embed" else shape[0])
    return n if name in _FLOAT32_LEAVES else n.astype(jnp.dtype(cfg["torch_dtype"]))


def init_params(key: jax.Array, cfg: dict) -> dict[str, jax.Array]:
    return {leaf_name(n, i): init_leaf(key, n, i, cfg) for n, i in all_leaves(cfg)}


# --- forward ----------------------------------------------------------------


def layer(lp: dict, x: jax.Array, cfg: dict, rounding=Rounding()) -> jax.Array:
    """One sandwich-normed block on x [B, S, d]."""
    hd = int(cfg["head_dim"])
    h, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    lp = {k: v.astype(jnp.float32) for k, v in lp.items()}

    def mm(a, w):
        return rounding.result(
            jnp.matmul(rounding.operand(a), rounding.operand(w), precision=_HIGH)
        )

    def one_sequence(xs):
        s = xs.shape[0]
        n = _rms_norm(xs, lp["attn_norm"], eps)
        q = _rope(mm(n, lp["wq"]).reshape(s, h, hd), theta)
        k = _rope(mm(n, lp["wk"]).reshape(s, kv, hd), theta)
        v = mm(n, lp["wv"]).reshape(s, kv, hd)
        a = mm(_attention(q, k, v, rounding), lp["wo"])
        xs = xs + _rms_norm(a, lp["attn_post_norm"], eps)
        n = _rms_norm(xs, lp["mlp_norm"], eps)
        m = mm(jax.nn.silu(mm(n, lp["w_gate"])) * mm(n, lp["w_up"]), lp["w_down"])
        return xs + _rms_norm(m, lp["mlp_post_norm"], eps)

    return lax.map(jax.checkpoint(one_sequence), x)


def head_unit(top: dict, h: jax.Array, targets: jax.Array, cfg: dict, rounding=Rounding()):
    """One pass's final norm, head, losses and gate on the stack's output
    h [B, S, d], `HEAD_ROWS` rows at a time: (n [B, S, d], l [B, S], z [B, S])."""
    eps = float(cfg["rms_norm_eps"])
    top = {k: v.astype(jnp.float32) for k, v in top.items()}
    w = rounding.operand(top["output"])

    @jax.checkpoint
    def one_block(ht):
        hb, t = ht
        n = _rms_norm(hb, top["final_norm"], eps)
        logits = rounding.result(jnp.matmul(rounding.operand(n), w, precision=_HIGH))
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, t[:, None], axis=-1)[:, 0]
        z = jnp.matmul(n, top["exit_gate_w"], precision=_HIGH) + top["exit_gate_b"]
        return n, nll, z

    b, s, d = h.shape
    rows = math.gcd(b * s, HEAD_ROWS)
    n, nll, z = lax.map(one_block, (h.reshape(-1, rows, d), targets.reshape(-1, rows)))
    return n.reshape(b, s, d), nll.reshape(b, s), z.reshape(b, s)


def exit_distribution(z: jax.Array) -> tuple[jax.Array, jax.Array]:
    """(log p, p) [T, ...] from the gate's logits z [T, ...], the last unread:
    log p from sums of log_sigmoid, not from the log of a product."""
    passes = z.shape[0]
    log_left = jnp.zeros_like(z[0])
    log_p = []
    for t in range(passes - 1):
        log_p.append(log_left + jax.nn.log_sigmoid(z[t]))
        log_left = log_left + jax.nn.log_sigmoid(-z[t])
    log_p = jnp.stack(log_p + [log_left])
    return log_p, jnp.exp(log_p)


def objective(nll: jax.Array, z: jax.Array, beta: float) -> jax.Array:
    """The training loss from every pass's l and z [T, B, S]; a sequence's
    last position has no next token and is left out."""
    log_p, p = exit_distribution(z)
    entropy = -jnp.sum(p * log_p, axis=0)
    token = jnp.sum(p * nll, axis=0) - beta * entropy
    b, s = token.shape
    return jnp.sum(token[:, :-1]) / (b * (s - 1))


def _stack(params: dict, cfg: dict) -> list[dict]:
    return [
        {n: params[leaf_name(n, i)] for n in LAYER_LEAVES}
        for i in range(int(cfg["num_hidden_layers"]))
    ]


def forward(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> dict:
    """The whole forward pass at once, for sizes where that fits (tests):
    every pass's logits [T, B, S, V], losses and gate logits [T, B, S], the
    exit distribution and the objective."""
    top = {n: params[n] for n in HEAD_LEAVES}
    x = embed(params["embed"], tokens)
    logits, nll, z = [], [], []
    for _ in range(int(cfg["total_ut_steps"])):
        for lp in _stack(params, cfg):
            x = layer(lp, x, cfg, rounding)
        x, l, g = head_unit(top, x, targets, cfg, rounding)
        logits.append(jnp.matmul(x, params["output"].astype(jnp.float32), precision=_HIGH))
        nll.append(l)
        z.append(g)
    nll, z = jnp.stack(nll), jnp.stack(z)
    return {
        "logits": jnp.stack(logits), "nll": nll, "gate": z, "p": exit_distribution(z)[1],
        "loss": objective(nll, z, float(cfg["exit_beta"])),
    }


def loss(params: dict, tokens, targets, cfg: dict, rounding=Rounding()) -> jax.Array:
    return forward(params, tokens, targets, cfg, rounding)["loss"]


# --- the steps, a block application at a time ---------------------------------


class _Pieces:
    """The jitted parts one configuration and precision need."""

    def __init__(self, cfg: dict, rounding):
        self.cfg = cfg
        self.layers = int(cfg["num_hidden_layers"])
        self.passes = int(cfg["total_ut_steps"])
        self.embed = jax.jit(embed)
        self.layer = jax.jit(partial(layer, cfg=cfg, rounding=rounding))
        head = partial(head_unit, cfg=cfg, rounding=rounding)
        self.head = jax.jit(head)
        self.objective = jax.jit(
            jax.value_and_grad(partial(objective, beta=float(cfg["exit_beta"])), argnums=(0, 1))
        )

        def layer_back(lp, x, dy):
            lp = {k: v.astype(jnp.float32) for k, v in lp.items()}
            _, pull = jax.vjp(partial(layer, cfg=cfg, rounding=rounding), lp, x)
            return pull(dy)

        self.layer_back = jax.jit(layer_back)

        def head_back(top, h, targets, cotangents):
            top = {k: v.astype(jnp.float32) for k, v in top.items()}
            _, pull = jax.vjp(lambda top, h: head(top, h, targets), top, h)
            return pull(cotangents)

        self.head_back = jax.jit(head_back)
        self.fresh = jax.jit(partial(init_leaf, cfg=cfg), static_argnums=(1, 2))
        self.embed_back = jax.jit(
            lambda tokens, dx, rows: jnp.zeros((rows, dx.shape[-1]), jnp.float32).at[tokens].add(dx),
            static_argnums=2,
        )

    def gradients(self, get, tokens, targets) -> tuple[jax.Array, dict]:
        """(the objective, {(leaf, layer): its gradient in float32}).
        `get(name, layer)` returns the leaf's current value."""
        stack = [{n: get(n, i) for n in LAYER_LEAVES} for i in range(self.layers)]
        top = {n: get(n, None) for n in HEAD_LEAVES}
        x = self.embed(get("embed", None), tokens)
        inputs, outputs, nll, z = [], [], [], []
        for _ in range(self.passes):
            for lp in stack:
                inputs.append(x)
                x = self.layer(lp, x)
            outputs.append(x)
            x, l, g = self.head(top, x, targets)
            nll.append(l)
            z.append(g)
        value, (d_nll, d_z) = self.objective(jnp.stack(nll), jnp.stack(z))
        del nll, z
        grads: dict = {}

        def gather(name, layer, g):
            key = (name, layer)
            grads[key] = grads[key] + g if key in grads else g

        dx = jnp.zeros_like(x)  # nothing reads the last pass's n
        del x
        for t in reversed(range(self.passes)):
            g_top, dx = self.head_back(top, outputs.pop(), targets, (dx, d_nll[t], d_z[t]))
            for n in HEAD_LEAVES:
                gather(n, None, g_top.pop(n))
            for i in reversed(range(self.layers)):
                g_layer, dx = self.layer_back(stack[i], inputs.pop(), dx)
                for n in LAYER_LEAVES:
                    gather(n, i, g_layer.pop(n))
        gather("embed", None, self.embed_back(tokens, dx, int(self.cfg["vocab_size"])))
        return value, grads


@lru_cache(maxsize=4)
def _pieces(cfg_json: str, precision: str) -> _Pieces:
    """Kept so that a process that follows many seeds traces them once."""
    return _Pieces(json.loads(cfg_json), ROUNDINGS[precision])


def _decayed(name: str) -> bool:
    """The trainer's mask: matrices alone; norm scales and the gate's vector
    and bias (rank 1 and 0) are exempt."""
    return not name.endswith("norm") and name not in _FLOAT32_LEAVES


def follow(key, cfg: dict, batches, steps: int, *, precision: str = "float32",
           batch_sharding=None) -> dict:
    """Follow the first one or two AdamW steps from the seeded weights.

    Returns each step's loss (the mixed objective), the norm and the seeded
    projection (`sketch.py`) per leaf of the first gradient as AdamW gets it
    (after clipping), and the norm per leaf of the parameters' change after
    the last step, as Python floats.
    """
    if steps not in (1, 2):
        raise ValueError(f"the looped decoder's reference follows 1 or 2 steps, not {steps}")
    pieces = _pieces(json.dumps(cfg, sort_keys=True), precision)
    lr, wd = float(cfg["learning_rate"]), float(cfg["weight_decay"])
    b1, b2, eps = float(cfg["adam_b1"]), float(cfg["adam_b2"]), float(cfg["adam_eps"])
    max_norm = float(cfg["grad_clip_norm"])

    def seeded(name, layer):
        return pieces.fresh(key, name, layer)

    def place(a):
        return jax.device_put(a, batch_sharding) if batch_sharding is not None else jnp.asarray(a)

    def clipped(grads):
        sumsq = {k: float(_sumsq(g)) for k, g in grads.items()}
        return sumsq, min(1.0, max_norm / math.sqrt(sum(sumsq.values())))

    with jax.default_matmul_precision("highest"):
        tokens, targets = (place(a) for a in batches[0])
        loss1, g1 = pieces.gradients(seeded, tokens, targets)
        sumsq1, clip1 = clipped(g1)
        out = {
            "loss": [float(loss1)],
            "grad_norm": {leaf_name(*k): clip1 * math.sqrt(v) for k, v in sumsq1.items()},
            "grad_sketch": {
                leaf_name(*k): [clip1 * float(x) for x in _sketch(g, leaf_name(*k), key)]
                for k, g in g1.items()
            },
            "head_leaves": list(HEAD_LEAVES),
        }
        p1 = {
            k: _adam_first(seeded(*k), g, clip1, lr, eps, wd, decay=_decayed(k[0]))
            for k, g in g1.items()
        }
        if steps == 1:
            out["update_norm"] = {
                leaf_name(*k): math.sqrt(float(_sumsq(p - seeded(*k).astype(jnp.float32))))
                for k, p in p1.items()
            }
            return out
        tokens2, targets2 = (place(a) for a in batches[1])
        loss2, g2 = pieces.gradients(lambda n, i: p1[(n, i)], tokens2, targets2)
        out["loss"].append(float(loss2))
        _, clip2 = clipped(g2)
        out["update_norm"] = {
            leaf_name(*k): math.sqrt(float(_adam_second(
                seeded(*k), p1[k], g1.pop(k), g2.pop(k), clip1, clip2,
                lr, b1, b2, eps, wd, decay=_decayed(k[0]),
            )))
            for k in list(p1)
        }
        return out
