"""chip_smoke.py and the start-up rules it rests on, as far as a CPU can
show them: the smoke refuses to pass without a TPU, its rehearsal size runs
every phase (kernels interpreted), the compile cache is placed from
outside or at one fixed path inside the checkout, the native build is
keyed on source content, and a fleet child that finds its chip taken dies
with an error its parent reports.
"""

import json
import os
import subprocess
import sys

import pytest

from bevy_ggrs_tpu.native import build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, env=None, cwd=REPO, timeout=300):
    """A fresh interpreter without the suite's 8-device flag and without a
    cache directory placed from outside, unless ``env`` places one."""
    full_env = {k: v for k, v in os.environ.items()
                if k not in ("XLA_FLAGS", "JAX_COMPILATION_CACHE_DIR")}
    full_env.update(env or {})
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True,
        env=full_env, cwd=cwd, timeout=timeout,
    )


def test_refuses_without_a_tpu():
    proc = _run([SMOKE], env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout == ""  # no result of any kind
    assert "needs a TPU" in proc.stderr


def test_fails_without_the_package(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(open(SMOKE).read())
    proc = _run([str(alone)], env={"JAX_PLATFORMS": "cpu", "PYTHONPATH": ""},
                cwd=str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""


def test_rehearsal_runs_every_phase_interpreted():
    proc = _run([SMOKE, "--rehearse"], env={"JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()]
    phases = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert list(phases) == [
        "identity", "timer_honesty", "singleton_pair", "served", "kernels",
    ]
    for ln in phases.values():
        assert ln["ok"] and ln["platform"] == "cpu"
        assert ln["size"] == "rehearsal"
    assert phases["identity"]["pallas_interpret"] is True
    assert phases["singleton_pair"]["oracle_state_bitwise"]
    assert phases["served"]["oracle_mismatches"] == []
    kernels = phases["kernels"]["kernels"]
    assert all(k["ok"] for k in kernels.values())
    assert not any(k.get("mosaic") for k in kernels.values())
    # A rehearsal is not a pass: the contract's last line never appears.
    assert not any("device" in ln for ln in lines)
    assert lines[-1] == {"rehearsal": "passed", "platform": "cpu",
                         "seconds": lines[-1]["seconds"]}


_PRINT_CACHE_DIR = (
    "from bevy_ggrs_tpu.utils.xla_cache import "
    "ensure_persistent_compilation_cache as f; print(f())"
)


def test_cache_dir_is_fixed_inside_the_checkout(tmp_path):
    seen = set()
    for cwd in (REPO, str(tmp_path)):
        proc = _run(["-c", _PRINT_CACHE_DIR], cwd=cwd, timeout=120,
                    env={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu"})
        assert proc.returncode == 0, proc.stderr[-2000:]
        seen.add(proc.stdout.strip())
    assert seen == {os.path.join(REPO, ".jax_cache")}


def test_cache_dir_obeys_the_environment(tmp_path):
    outside = str(tmp_path / "placed_from_outside")
    proc = _run(["-c", _PRINT_CACHE_DIR], cwd=str(tmp_path), timeout=120,
                env={"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
                     "JAX_COMPILATION_CACHE_DIR": outside})
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert proc.stdout.strip() == outside


def test_only_xla_cache_sets_the_cache_dir():
    offenders = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if not d.startswith(".")
                   and d != "chiprun_out"]
        for name in files:
            path = os.path.join(root, name)
            if not name.endswith(".py") or path == os.path.abspath(__file__):
                continue
            with open(path) as f:
                if "jax_compilation_cache_dir" in f.read():
                    offenders.append(os.path.relpath(path, REPO))
    assert offenders == [os.path.join("bevy_ggrs_tpu", "utils",
                                      "xla_cache.py")]


def test_native_build_is_keyed_on_content_not_mtime(tmp_path):
    src = tmp_path / "probe.cpp"
    src.write_text('extern "C" int probe() { return 1; }\n')
    first = build.build_lib(str(src))
    assert os.path.basename(first).startswith("_probe-")
    built_at = os.stat(first).st_mtime_ns

    # A copy's mtimes mean nothing: newer source, same text -> no rebuild.
    later = os.stat(src).st_mtime + 3600
    os.utime(src, (later, later))
    assert build.build_lib(str(src)) == first
    assert os.stat(first).st_mtime_ns == built_at

    # Other text -> another library, and the stale one goes.
    src.write_text('extern "C" int probe() { return 2; }\n')
    second = build.build_lib(str(src))
    assert second != first and os.path.exists(second)
    assert not os.path.exists(first)

    # The same text again, with an OLDER mtime than the library's.
    os.utime(src, (1, 1))
    assert build.build_lib(str(src)) == second


def test_native_build_failure_is_an_error(tmp_path):
    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++\n")
    with pytest.raises(RuntimeError, match="native build failed"):
        build.build_lib(str(src))


def test_compile_counters_tell_hits_from_misses(tmp_path):
    """jax reports a cache-served executable under the same duration event
    as a compiled one; the hit/miss events are what tell them apart."""
    script = (
        "import jax, jax.numpy as jnp, json\n"
        "from bevy_ggrs_tpu.utils import xla_cache as x\n"
        "x.ensure_persistent_compilation_cache()\n"
        "x.install_compile_listeners()\n"
        "jax.jit(lambda a: jnp.sin(a) * 3 + 1)(jnp.ones(7))"
        ".block_until_ready()\n"
        "print(json.dumps([x.compile_counters(), x.compile_events()[-1]]))\n"
    )
    env = {"PYTHONPATH": REPO, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
    cold = _run(["-c", script], env=env, timeout=120)
    warm = _run(["-c", script], env=env, timeout=120)
    assert cold.returncode == 0 and warm.returncode == 0, warm.stderr[-2000:]
    c, c_last = json.loads(cold.stdout.splitlines()[-1])
    w, w_last = json.loads(warm.stdout.splitlines()[-1])
    assert c["cache_misses"] == c["backend_compiles"] > 0
    assert c["cache_hits"] == 0 and c_last["cache"] == "miss"
    assert w["cache_hits"] == w["backend_compiles"] == c["backend_compiles"]
    assert w["cache_misses"] == 0 and w_last["cache"] == "hit"
    assert c_last["fingerprint"] == w_last["fingerprint"] != ""


def test_fleet_child_without_its_chip_dies_and_the_parent_says_why(
    tmp_path, monkeypatch
):
    """This installation carries the TPU runtime and this machine has no
    chip to give: with no platform named, jax would bring the child up on
    the CPU without a word — the same thing that happens on a one-chip
    host whose chip the parent (or a sibling) already holds."""
    from bevy_ggrs_tpu.fleet.proc import ProcFleet

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    fleet = ProcFleet(str(tmp_path))
    try:
        with pytest.raises(RuntimeError, match="one process per chip"):
            fleet.spawn_server(wait_ready=True, timeout=120.0)
    finally:
        fleet.close()
