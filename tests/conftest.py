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

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def one_program_ticks():
    """A speculative runner decides at warm-up, from two times it takes on
    its own executables, whether a tick goes out as one program or as two
    (``spec_runner.py`` ``_decide_split``). At this suite's toy sizes on a
    CPU the two times are close, the choice would differ from run to run
    and every count of dispatches with it: the suite's runners take no
    times and keep the one program. ``tests/test_split_tick.py`` injects
    the times it wants."""
    from bevy_ggrs_tpu import spec_runner

    # A patch of its own: a test may undo() the shared ``monkeypatch``.
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(spec_runner, "_blocking_ms", lambda call, reps=3: 0.0)
        yield
