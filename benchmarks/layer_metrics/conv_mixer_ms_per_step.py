"""Trainer: device time of the gated short convolutions (everything under the
scope `conv`: the input projection, the gates and the taps, the output
projection; forward, recomputed and backward), per executed program of the
traced window on device 0, in milliseconds.  The scopes this kind of model adds
to `moe_reduce.SCOPES`' table go to the notes beside it."""

from benchmarks import moe_reduce

SCOPES = {
    "conv": ("in", "core", "out"),
    "attn": ("qkv", "qk_norm"),
    "loss": ("operator_norm", "ffn_norm"),
}


def read(run: dict) -> float | None:
    total = moe_reduce.scope_ms_per_step(run, ("conv",))
    if total is None:
        return None
    table = {
        f"{parent}/{scope}": moe_reduce.scope_ms_per_step(run, (parent, scope))
        for parent, scopes in SCOPES.items() for scope in scopes
    }
    run.setdefault("notes", {})["conv_scope_ms_per_step"] = {
        k: v for k, v in table.items() if v is not None
    }
    return total
