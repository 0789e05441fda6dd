"""Test harness: force an 8-device virtual CPU mesh before JAX loads.

SURVEY §4's prescription for SPMD tests without a pod:
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` with the CPU
platform, so every sharding/collective path compiles and executes exactly
as it would over an 8-chip slice.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# Test isolation: entry points enable a persistent XLA compile cache
# inside the checkout (utils/compile_cache.py); tests — including the
# ones spawning example subprocesses — switch it off through JAX's own
# flag, so a thousand CPU programs never land in the tree the chip tool
# copies.  setdefault so an operator can opt a run back in.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture()
def contract_root(tmp_path, monkeypatch):
    """Redirect the cluster-contract publication dir away from /opt."""
    root = tmp_path / "opt-deeplearning"
    monkeypatch.setenv("DLCFN_ROOT", str(root))
    return root
