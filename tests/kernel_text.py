"""What several test files read out of a traced or lowered program: the
equations of a jaxpr by primitive, the Pallas kernels a jaxpr calls by name,
and the flash kernels' Mosaic modules without source locations."""

import base64
import collections
import hashlib
import re

import jax
import jax.numpy as jnp

from deeplearning_cfn_tpu.ops import pallas_attention


def equations(jaxpr):
    """Every equation of a jaxpr and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax.core.jaxprs_in_params(eqn.params):
            yield from equations(inner)


def named(jaxpr, primitive):
    return [e for e in equations(jaxpr) if e.primitive.name == primitive]


def kernel_calls(jaxpr) -> collections.Counter:
    """name -> how many `pallas_call` equations of that name the jaxpr holds."""
    return collections.Counter(e.params["name"] for e in named(jaxpr, "pallas_call"))


def kernels_without_locations(window):
    """name -> sha256 of the Mosaic kernel's MLIR printed without source
    locations, lowered for the TPU at a small shape (no chip needed)."""
    def loss(q, k, v):
        return pallas_attention.flash_attention(q, k, v, window=window).astype(jnp.float32).sum()

    q = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 256, 2, 128), jnp.bfloat16)
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(q, kv, kv).lower(
        lowering_platforms=("tpu",)
    ).as_text()
    return {
        name: hashlib.sha256(_kernel_asm(body).encode()).hexdigest()
        for body, name in re.findall(_KERNEL_BODY + r'.*?kernel_name = "([^"]+)"', text)
    }


_KERNEL_BODY = r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22'


def _kernel_asm(body: str) -> str:
    """A serialized Mosaic kernel's MLIR, printed without source locations."""
    from jax._src import tpu_custom_call  # noqa: F401  (registers the TPU dialect)
    from jax._src.lib.mlir import ir

    context = ir.Context()
    context.allow_unregistered_dialects = True
    with context:
        return ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)


def text_without_kernel_locations(text: str) -> str:
    """A program lowered for the TPU with each Mosaic kernel's body replaced by
    the sha256 of its MLIR without source locations: what two checkouts of the
    same program agree on, whatever their paths and line numbers."""
    return re.sub(
        _KERNEL_BODY,
        lambda m: '\\22body\\22: \\22' + hashlib.sha256(_kernel_asm(m.group(1)).encode()).hexdigest() + '\\22',
        text,
    )
