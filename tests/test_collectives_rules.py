"""DLC5xx comms/memory fixtures: every rule fires on its seeded bug and
stays silent on the repo's sanctioned idiom (docs/STATIC_ANALYSIS.md).

Like the DLC4xx pass, the comms pass is *gated*: a plain ``lint_source``
(select=None) must never run it, so each case passes an explicit
``select`` — exactly how the runner enables it under
``dlcfn lint --comms``.  Fixture paths live under ``train/`` because the
pass scopes itself to the comms-relevant tree (train/, parallel/,
models/, ops/, serve/, bench.py).
"""

import textwrap

from deeplearning_cfn_tpu.analysis import lint_source
from deeplearning_cfn_tpu.analysis.collectives import (
    AUDIT_RULE_IDS,
    RULE_IDS,
)

COMPUTE_PATH = "deeplearning_cfn_tpu/train/x.py"


def rules_for(src: str, select: set[str], path: str = COMPUTE_PATH):
    return [v.rule for v in lint_source(path, textwrap.dedent(src), select=select)]


# --- the gate itself --------------------------------------------------------


def test_gated_rules_do_not_run_without_select():
    """Growing the DLC5xx set must never change a plain `dlcfn lint`."""
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        step = jax.jit(f, in_shardings=(P("dp", None),), out_shardings=(P(None, None),))
    """
    fired = [v.rule for v in lint_source(COMPUTE_PATH, textwrap.dedent(src))]
    assert not set(fired) & set(RULE_IDS)
    assert rules_for(src, select={"DLC500"}) == ["DLC500"]


def test_rules_scope_to_the_comms_tree():
    """The same seeded bug under cluster/ is out of scope — but unlike
    DLC4xx, parallel/ IS in scope: it authors the sharding helpers."""
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        step = jax.jit(f, in_shardings=(P("dp", None),), out_shardings=(P(None, None),))
    """
    assert rules_for(src, {"DLC500"}, path="deeplearning_cfn_tpu/cluster/x.py") == []
    assert rules_for(
        src, {"DLC500"}, path="deeplearning_cfn_tpu/parallel/x.py"
    ) == ["DLC500"]
    assert rules_for(src, {"DLC500"}, path="deeplearning_cfn_tpu/serve/x.py") == [
        "DLC500"
    ]


def test_noqa_suppresses_with_reason():
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        step = jax.jit(f, in_shardings=(P("dp", None),), out_shardings=(P(None, None),))  # dlcfn: noqa[DLC500] gather at the boundary is intended here
    """
    assert rules_for(src, {"DLC500"}) == []


def test_audit_rule_ids_are_reserved_not_static():
    """DLC510/511/512 belong to the dynamic sentinel: no static rule may
    claim them, so the baseline namespaces stay disjoint."""
    assert set(AUDIT_RULE_IDS) == {"DLC510", "DLC511", "DLC512"}
    assert not set(AUDIT_RULE_IDS) & set(RULE_IDS)


# --- DLC500: pjit in/out spec consistency ------------------------------------


def test_dlc500_fires_on_axis_dropped_between_in_and_out():
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        step = jax.jit(f, in_shardings=(P("dp", None),), out_shardings=(P(None, None),))
    """
    assert rules_for(src, {"DLC500"}) == ["DLC500"]


def test_dlc500_fires_on_axis_appearing_only_in_out():
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        step = jax.jit(f, in_shardings=(P(None, None),), out_shardings=(P("tp", None),))
    """
    assert rules_for(src, {"DLC500"}) == ["DLC500"]


def test_dlc500_fires_on_unknown_axis_name():
    """An axis outside parallel/mesh.py AXIS_ORDER silently degrades
    that side of the contract to replication — one finding per use."""
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        step = jax.jit(f, in_shardings=(P("model"),), out_shardings=(P("model"),))
    """
    assert rules_for(src, {"DLC500"}) == ["DLC500", "DLC500"]


def test_dlc500_quiet_on_matching_specs_and_shared_sharding_objects():
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        a = jax.jit(f, in_shardings=(P("dp", None),), out_shardings=(P("dp", None),))
        b = jax.jit(g, in_shardings=state_sh, out_shardings=state_sh)
    """
    assert rules_for(src, {"DLC500"}) == []


# --- DLC501: unconstrained large intermediate --------------------------------


def test_dlc501_fires_on_named_matmul_chain_without_constraint():
    src = """\
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        @jax.jit
        def step(x, w1, w2):
            x = jax.lax.with_sharding_constraint(x, P("fsdp", None))
            h = jnp.matmul(x, w1)
            return jnp.matmul(h, w2)
    """
    assert rules_for(src, {"DLC501"}) == ["DLC501"]


def test_dlc501_fires_on_directly_nested_matmuls():
    """Consumer wraps producer in one expression: nowhere to constrain."""
    src = """\
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        @jax.jit
        def step(x, w1, w2):
            x = jax.lax.with_sharding_constraint(x, P("fsdp", None))
            return jnp.matmul(jnp.matmul(x, w1), w2)
    """
    assert rules_for(src, {"DLC501"}) == ["DLC501"]


def test_dlc501_quiet_when_intermediate_is_constrained():
    src = """\
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        @jax.jit
        def step(x, w1, w2):
            h = jnp.matmul(x, w1)
            h = jax.lax.with_sharding_constraint(h, P("fsdp", None))
            return jnp.matmul(h, w2)
    """
    assert rules_for(src, {"DLC501"}) == []


def test_dlc501_quiet_in_files_that_never_author_shardings():
    """No constraint call and no sharding kwarg anywhere in the file
    means single-device code: layout inference has nothing to get
    wrong, so matmul chains are fine."""
    src = """\
        import jax
        import jax.numpy as jnp

        @jax.jit
        def step(x, w1, w2):
            h = jnp.matmul(x, w1)
            return jnp.matmul(h, w2)
    """
    assert rules_for(src, {"DLC501"}) == []


# --- DLC502: host materialization of a sharded array -------------------------


def test_dlc502_fires_on_np_asarray_of_sharded_array():
    src = """\
        import jax
        import numpy as np

        def fetch(x, sharding):
            y = jax.device_put(x, sharding)
            return np.asarray(y)
    """
    assert rules_for(src, {"DLC502"}) == ["DLC502"]


def test_dlc502_fires_on_item_of_constrained_array():
    src = """\
        import jax
        from jax.sharding import PartitionSpec as P

        def loss_value(x):
            loss = jax.lax.with_sharding_constraint(x, P("dp"))
            return loss.item()
    """
    assert rules_for(src, {"DLC502"}) == ["DLC502"]


def test_dlc502_quiet_on_unsharded_device_put():
    """device_put without a sharding is single-device placement —
    pulling it back is a plain copy, not an all-gather."""
    src = """\
        import jax
        import numpy as np

        def fetch(x):
            y = jax.device_put(x)
            return np.asarray(y)
    """
    assert rules_for(src, {"DLC502"}) == []


# --- DLC503: cross-mesh leakage ----------------------------------------------


def test_dlc503_fires_on_bare_dispatch_after_set_mesh_dispatch():
    src = """\

        def bench(trainer, state, x, mesh):
            step = trainer.step_fn
            with jax.set_mesh(mesh):
                state = step(state, x)
            metrics = step(state, x)
            return metrics
    """
    assert rules_for(src, {"DLC503"}) == ["DLC503"]


def test_dlc503_quiet_when_every_dispatch_shares_the_mesh():
    src = """\

        def bench(trainer, state, x, mesh):
            step = trainer.step_fn
            with jax.set_mesh(mesh):
                state = step(state, x)
                metrics = step(state, x)
            return metrics
    """
    assert rules_for(src, {"DLC503"}) == []


# --- DLC504: shard_map reduction without a named collective ------------------


def test_dlc504_fires_on_local_mean_without_psum():
    src = """\
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map

        def local_mean(x):
            return jnp.mean(x)

        def run(mesh, x):
            fn = shard_map(local_mean, mesh=mesh, in_specs=None, out_specs=None)
            return fn(x)
    """
    assert rules_for(src, {"DLC504"}) == ["DLC504"]


def test_dlc504_quiet_when_body_carries_a_named_collective():
    src = """\
        import jax
        import jax.numpy as jnp
        from jax.experimental.shard_map import shard_map

        def global_mean(x):
            s = jnp.sum(x)
            return jax.lax.psum(s, "dp") / x.size

        def run(mesh, x):
            fn = shard_map(global_mean, mesh=mesh, in_specs=None, out_specs=None)
            return fn(x)
    """
    assert rules_for(src, {"DLC504"}) == []


# --- DLC505: donated buffer read after the donating call ---------------------


def test_dlc505_fires_on_read_after_donation():
    src = """\
        import jax

        step = jax.jit(train, donate_argnums=(0,))

        def loop(state, batch):
            new_state, loss = step(state, batch)
            checkpoint(state)
            return new_state, loss
    """
    assert rules_for(src, {"DLC505"}) == ["DLC505"]


def test_dlc505_quiet_when_name_rebinds_through_the_call():
    """The repo idiom: `state, _ = step(state, ...)` launders the name."""
    src = """\
        import jax

        step = jax.jit(train, donate_argnums=(0,))

        def loop(state, batch):
            state, loss = step(state, batch)
            checkpoint(state)
            return state, loss
    """
    assert rules_for(src, {"DLC505"}) == []
