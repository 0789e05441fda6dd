"""Trainer: device time of the Mamba-2 mixers (everything under the scope
`ssm`: the input projection, the convolution, the scan, the gated norm, the
output projection; forward, recomputed and backward), per executed program of
the traced window on device 0, in milliseconds.  The scopes this kind of model
adds to `moe_reduce.SCOPES`' table go to the notes beside it."""

from benchmarks import moe_reduce

SCOPES = {
    "ssm": ("in_proj", "conv", "scan", "gate_norm", "out_proj"),
    "attn": ("qkv",),
    "moe": ("latent_in", "latent_out"),
    "loss": ("ssm_norm", "moe_norm"),
}


def read(run: dict) -> float | None:
    total = moe_reduce.scope_ms_per_step(run, ("ssm",))
    if total is None:
        return None
    table = {
        f"{parent}/{scope}": moe_reduce.scope_ms_per_step(run, (parent, scope))
        for parent, scopes in SCOPES.items() for scope in scopes
    }
    run.setdefault("notes", {})["ssm_scope_ms_per_step"] = {
        k: v for k, v in table.items() if v is not None
    }
    return total
