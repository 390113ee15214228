"""Test config: run everything on a virtual 8-device CPU mesh.

The virtual-device XLA flag must be in place before any backend
initializes, so this file sets it (and pins the CPU platform) before it
imports jax. Tests then never need an accelerator and get a deterministic
8-device mesh for sharding coverage.

Set ``GGRS_TEST_TPU=1`` to run the suite against the real default backend
instead (Pallas kernels then execute compiled rather than interpreted;
multi-device sharding tests will skip if only one chip is visible).
"""

import os

if os.environ.get("GGRS_TEST_TPU") != "1":
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"

# Persistent XLA compilation cache: the suite's dominant cost is compiling
# per-test executables (every runner's schedule closure is a fresh jit
# entry), and the programs are identical across runs — a warm cache cuts
# attestation-heavy test files ~3x (measured 28 -> 10 s). Keyed by HLO
# hash, so stale entries are impossible; delete the dir to force cold.
from bevy_ggrs_tpu.utils.xla_cache import (  # noqa: E402
    ensure_persistent_compilation_cache,
)

ensure_persistent_compilation_cache()
