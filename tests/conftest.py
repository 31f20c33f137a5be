"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths
compile and execute on a single machine (the driver separately dry-runs the
multi-chip path via __graft_entry__.dryrun_multichip).
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

# Tests run on the CPU backend with 8 virtual devices, whatever the machine
# has: the config update also covers a jax that something imported before
# this file set the variable.
try:
    import jax

    jax.config.update("jax_platforms", "cpu")
except Exception:
    pass

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import asyncio  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def run_async():
    """Run a coroutine to completion on a fresh event loop."""

    def _run(coro, timeout=60):
        async def _with_timeout():
            return await asyncio.wait_for(coro, timeout)

        return asyncio.run(_with_timeout())

    return _run


@pytest.fixture
def kernel_on_cpu(monkeypatch):
    """``ops/bitview.py``'s rows kernel as the chip runs it, interpreted:
    the CPU backend's serving path is the flat form, so a test says where
    the kernel may run."""
    from dragonfly2_tpu.ops import bitview

    monkeypatch.setattr(bitview, "_KERNEL_PLATFORMS", ("tpu", "cpu"))


@pytest.fixture
def fresh_compiles():
    """Neither a persistent compilation cache nor an assembly program in
    memory, and the thread's compiles counted: whatever geometry a sink
    meets in the test it compiles, once, whatever an earlier run left on
    disk or an earlier test in this worker landed. Yields ``ops.hbm_sink``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from dragonfly2_tpu.ops import hbm_sink

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    hbm_sink._assemble_checksum_jit.clear_cache()
    hbm_sink.watch_compiles()
    yield hbm_sink
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()
