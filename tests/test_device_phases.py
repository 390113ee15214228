"""Device scopes and the phase map (PR 50).

``obs/trace.py`` ``device_scope`` names the pieces of a jitted program as a
span names a stretch of the host's work; ``utils.xla_cache.
record_executable_cost`` reads the names back off the compiled executable
(``executable_phases()``: instruction name -> scopes), which joined with a
device trace's operation times is device time by phase
(``benchmark/readers/trace_phase.py``, ``tools/trace_spans.py server``).
Here, on the CPU at toy sizes: what the map of the batched tick holds, when
it is captured and when not, that the persistent cache cannot hand the
capture somebody else's names, that the scopes leave the computation alone,
and the tool's table.
"""

import contextlib
import importlib.util
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bevy_ggrs_tpu import fused
from bevy_ggrs_tpu.fused import PackedTick, TickInts
from bevy_ggrs_tpu.models import box_game, particles
from bevy_ggrs_tpu.obs import trace as obs_trace
from bevy_ggrs_tpu.obs.trace import SpanTracer, device_scope
from bevy_ggrs_tpu.ops import checksum as checksum_ops
from bevy_ggrs_tpu.ops import lifecycle, pairwise
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.state import SnapshotRing, ring_init
from bevy_ggrs_tpu.utils import xla_cache
from bevy_ggrs_tpu.utils.metrics import Metrics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
P = 2
PHASES = {"absorb", "burst", "rollout", "carry_codec"}
# What the compiler makes on its own (copies, the broadcasts of constants)
# and the scalars sliced off the int32 argument have no metadata that names
# a scope; they take the scopes of the operation they feed. Left with none:
# what feeds only the program's outputs, at most this share of the entry
# computation.
ENTRY_UNSCOPED_SHARE = 0.10

TITLES = {
    # title -> (schedule, world, input spec, the inner scopes it uses)
    "box_game": lambda: (
        box_game.make_schedule(), box_game.make_world(P).commit(),
        box_game.INPUT_SPEC,
        {"schedule", "ring_write", "ring_read", "checksum", "commit"},
    ),
    # Births claim rows and a row is 72 KiB: kept flat through a burst.
    "particles": lambda: (
        particles.make_schedule(), particles.make_world(P).commit(),
        particles.INPUT_SPEC,
        {"schedule", "ring_write", "ring_read", "checksum", "row_layout",
         "claim", "commit"},
    ),
}


@pytest.fixture
def fresh_captures(monkeypatch):
    """The process-wide capture tables, empty for one test."""
    monkeypatch.setattr(xla_cache, "_EXEC_COSTS", {})
    monkeypatch.setattr(xla_cache, "_EXEC_PHASES", {})
    monkeypatch.delenv("GGRS_XLA_COST", raising=False)


def make_core(title="box_game", **sinks):
    schedule, world, input_spec, _ = TITLES[title]()
    return BatchedSessionCore(
        schedule, world, 4, P, input_spec, num_slots=2, num_branches=2,
        spec_frames=3, predictor=False, **sinks)


def test_device_scope_is_a_named_scope_under_the_spans_prefix():
    def f(x):
        with device_scope("ring_write"):
            with device_scope("checksum"):
                return x + 1

    (eqn,) = jax.make_jaxpr(f)(1.0).eqns
    assert str(eqn.source_info.name_stack) == "ggrs/ring_write/ggrs/checksum"
    assert obs_trace.TRACE_PREFIX == "ggrs/"
    # The kernel's scope is one of them, its string what the accepted
    # ``pairwise_kernel_ms.*`` match.
    assert pairwise.FORCE_SCOPE == "ggrs/pairwise_force"


@pytest.mark.parametrize("title", sorted(TITLES))
def test_the_batched_ticks_map(title, fresh_captures, monkeypatch):
    """A core with a real sink captures, at warm-up, a map in which all
    four phases and every inner scope the title uses occur; every
    instruction inside the burst's and the rollout's loops has that loop's
    phase; little of the entry computation has none."""
    texts = []
    parse = xla_cache.op_scopes
    monkeypatch.setattr(xla_cache, "op_scopes",
                        lambda text: texts.append(text) or parse(text))
    core = make_core(title, metrics=Metrics())
    core.warmup()
    (name,) = xla_cache.executable_phases()
    assert name == "batched_tick_S2_B2_F3"
    assert xla_cache.executable_costs()[name]  # ONE capture: the cost too
    record = xla_cache.executable_phases()[name]
    ops = record["ops"]
    assert {scopes[0] for scopes in ops.values()} == PHASES
    inner = {s for scopes in ops.values() for s in scopes[1:]}
    assert TITLES[title]()[3] <= inner
    if title == "box_game":     # rows of 192 bytes stay shaped, no births
        assert not inner & {"row_layout", "claim"}
    # ``claim`` lies inside ``schedule``, as the kernel's scope would.
    for scopes in ops.values():
        if "claim" in scopes:
            assert "schedule" in scopes[:scopes.index("claim")]

    (text,) = texts
    rows = parse(text)
    assert record["unscoped"] == sum(not r.scopes for r in rows)
    assert record["inherited"] == sum(
        bool(r.scopes) and not r.own for r in rows) > 0
    assert ops == {r.name: r.scopes for r in rows if r.scopes}
    # The loops: a ``while`` traced directly under a phase, and its body.
    bodies = {}
    for line in text.splitlines():
        m = re.search(r" while\(.*\bbody=%?([\w.-]+).*op_name=\"([^\"]*)\"",
                      line)
        if m:
            parts = re.findall(r"ggrs/(\w+)", m.group(2))
            bodies[m.group(1)] = parts[0]
    assert {"burst", "rollout"} <= set(bodies.values())
    for body, phase in bodies.items():
        inside = [r for r in rows if r.computation == body]
        assert inside
        assert all(r.scopes and r.scopes[0] == phase for r in inside), [
            r for r in inside if not r.scopes or r.scopes[0] != phase]
    entry = [r for r in rows if r.entry]
    bare = [r for r in entry if not r.scopes]
    assert len(bare) <= ENTRY_UNSCOPED_SHARE * len(entry), (
        len(bare), len(entry), [(r.name, r.opcode) for r in bare])
    # Outside the entry computation nothing is without a phase.
    assert all(r.scopes for r in rows if not r.entry)


@pytest.mark.parametrize("arm", ["nothing", "env", "metrics", "tracer"])
def test_who_arms_the_capture(arm, fresh_captures, monkeypatch):
    """``GGRS_XLA_COST`` or a real sink arm ONE capture; a core with both
    sinks null never lowers its tick a second time."""
    calls = []
    record = xla_cache.record_executable_cost
    monkeypatch.setattr(
        xla_cache, "record_executable_cost",
        lambda name, *a, **k: calls.append(name) or record(name, *a, **k))
    sinks = {}
    if arm == "env":
        monkeypatch.setenv("GGRS_XLA_COST", "1")
    elif arm == "metrics":
        sinks["metrics"] = Metrics()
    elif arm == "tracer":
        sinks["tracer"] = SpanTracer()
    core = make_core(**sinks)
    traced = []
    tick = core._exec.packed.tick
    monkeypatch.setattr(core._exec.packed, "tick",
                        lambda *a: traced.append(1) or tick(*a))
    lowered = []
    fn = core._exec._fn

    class Spy:  # the jitted tick, its AOT door counted
        def __call__(self, *a):
            return fn(*a)

        def lower(self, *a, **k):
            lowered.append(1)
            return fn.lower(*a, **k)

        def __getattr__(self, name):
            return getattr(fn, name)

    core._exec._fn = Spy()
    core.warmup()
    core.tick({})
    if arm == "nothing":
        assert calls == [] and lowered == [] and len(traced) == 1
        assert xla_cache.executable_phases() == {}
        assert xla_cache.executable_costs() == {}
        assert core._exec.cost() == {}
    else:
        assert calls == ["batched_tick_S2_B2_F3"] and lowered == [1]
        assert set(xla_cache.executable_phases()) == set(calls)
        assert core._exec.cost()["hbm_peak_bytes"] > 0
    assert core._exec.cache_size() == 1


def _twin(scoped: bool):
    """The same function with and without device scopes: one optimized
    computation, two sets of names."""
    scope = device_scope if scoped else (lambda name: contextlib.nullcontext())

    def tick(x):
        with scope("burst"):
            y = jnp.sin(x) * 2.0
            with scope("checksum"):
                z = jnp.sum(y.astype(jnp.int32), axis=0)
        with scope("rollout"):
            return jnp.cos(y) + 1.0, z

    return jax.jit(tick)


# A process of its own: the cache directory is placed from outside (the
# program's rule: only ``utils/xla_cache.py`` names that option), empty.
TRAP = """
import json, os, sys
sys.path[:0] = [{root!r}, {tests!r}]
import jax.numpy as jnp
from bevy_ggrs_tpu.utils import xla_cache
from test_device_phases import _twin

assert xla_cache.ensure_persistent_compilation_cache() == {cache!r}
x = jnp.arange(24.0).reshape(4, 6)
twin = _twin(False).lower(x).compile().as_text()
entries = len(os.listdir({cache!r}))
hit = _twin(True).lower(x).compile().as_text()
xla_cache.record_executable_cost("twin", _twin(True), x)
import jax
print(json.dumps({{
    "twin": "ggrs/" in twin, "entries": entries, "hit": "ggrs/" in hit,
    "key": jax.config.jax_compilation_cache_include_metadata_in_key,
    "ops": {{k: list(v) for k, v in
            xla_cache.executable_phases()["twin"]["ops"].items()}}}}))
"""


def test_the_capture_is_not_served_another_trees_names(tmp_path):
    """The persistent cache drops metadata from its key: once an unscoped
    twin filled it, a plain compile of the scoped function is a HIT of the
    twin's executable, whose text names no scope. The capture keys its one
    compile with the metadata and still returns the full map."""
    cache = str(tmp_path / "cache")
    os.mkdir(cache)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=cache)
    env.pop("JAX_ENABLE_COMPILATION_CACHE", None)
    script = TRAP.format(root=ROOT, tests=os.path.join(ROOT, "tests"),
                         cache=cache)
    done = subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.strip().splitlines()[-1])
    assert out["entries"] > 0       # the twin's entry
    assert not out["twin"]
    assert not out["hit"]           # the trap: the twin's names
    assert not out["key"]           # the option is back where it was
    ops = out["ops"].values()
    assert {scopes[0] for scopes in ops} == {"burst", "rollout"}
    assert ["burst", "checksum"] in ops


def test_a_program_without_scopes_has_an_empty_map(fresh_captures):
    xla_cache.record_executable_cost("bare", _twin(False), jnp.ones((4, 6)))
    record = xla_cache.executable_phases()["bare"]
    assert record["ops"] == {} and record["unscoped"] > 0


def _single_session_programs():
    """``PackedTick``'s three programs for one box_game session, lowered
    and compiled: the fused tick, the front, the absorb."""
    MF, B, F = 4, 2, 3
    schedule = box_game.make_schedule()
    state = jax.tree_util.tree_map(
        jnp.asarray, box_game.make_world(P).commit())
    stack = lambda *lead: jax.tree_util.tree_map(       # noqa: E731
        lambda x: jnp.broadcast_to(x, lead + x.shape), state)
    prev_rings = SnapshotRing(
        states=stack(B, F), frames=jnp.full((B, F), -1, jnp.int32),
        checksums=jnp.zeros((B, F, 2), jnp.uint32))
    trees = (ring_init(state, MF - 1), state, prev_rings, stack(B))
    packed = PackedTick(schedule, MF, B, F)
    packed.bind(trees)
    carry = packed.pack(*trees)
    ints = TickInts.zeros(MF, P)
    bits = np.zeros((MF, P), np.uint8)
    branch_bits = np.zeros((B, F, P), np.uint8)
    # Keyed with the metadata, as the capture's compile is: the persistent
    # cache must not answer with a twin's names.
    def text(fn, *args):
        lowered = jax.jit(fn).lower(carry, *args)
        return xla_cache._compile_keyed_with_metadata(lowered).as_text()

    return {
        "tick": text(packed.tick, ints, bits, branch_bits),
        "front": text(packed.front, ints, bits),
        "absorb": text(packed.absorb, ints[:TickInts.ABSORB]),
    }


def test_the_front_and_the_absorb_program_trace_the_same_scopes():
    scopes = {}
    for program, text in _single_session_programs().items():
        rows = [r for r in xla_cache.op_scopes(text) if r.scopes]
        scopes[program] = ({r.scopes[0] for r in rows},
                           {s for r in rows for s in r.scopes[1:]})
    phases, inner = scopes["tick"]
    assert phases == PHASES
    assert inner == {"schedule", "ring_write", "ring_read", "checksum",
                     "commit"}
    assert scopes["front"] == (PHASES - {"rollout"}, inner)
    assert scopes["absorb"][0] == {"absorb", "carry_codec"}
    assert scopes["absorb"][1] == {"ring_write", "ring_read", "commit"}


def _computation(text: str) -> str:
    """An optimized module with what only names it taken out: the
    metadata, the tables it points into, and the instructions' own names
    (jax shares the lowering of an inner ``jit`` between call sites of one
    name stack, so scopes move the serial numbers, and with them which of
    two merged instructions' names survives): every instruction's opcode,
    shape, layout, attributes and operand count, in order."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n"
                  r"(?:\d+ .*\n)*", "", text, flags=re.M)
    return re.sub(r"%[\w.-]+", "%", text)


def test_the_scopes_leave_the_computation_alone(monkeypatch):
    """Scopes are trace-time metadata: with ``metadata=`` and the
    instructions' names taken out, the optimized module is the unscoped
    program's, character for character."""
    scoped = _single_session_programs()
    assert "ggrs/burst" in scoped["tick"]
    with monkeypatch.context() as patch:
        off = lambda name: contextlib.nullcontext()  # noqa: E731
        patch.setattr(obs_trace, "device_scope", off)  # schedule, state
        for module in (fused, lifecycle, checksum_ops):
            patch.setattr(module, "device_scope", off)
        bare = _single_session_programs()
    for program in scoped:
        assert "ggrs/" not in bare[program].replace("ggrs/pairwise", "")
        assert _computation(scoped[program]) == _computation(bare[program])


# The burst's loop of the served particles tick as the TPU's compiler prints
# it (PR 53; shapes shortened): the ring leaf's save is ONE custom call, the
# Mosaic kernel of ``ops/ring_write.py``, and the copy that retiles its row
# has no metadata of its own.
_BURST_WITH_THE_KERNEL = """\
HloModule jit__tick_impl, is_scheduled=true

%region_7.94 (arg_tuple.0: (s32[], f32[64,9,144,128])) -> (s32[], f32[64,9,144,128]) {
  %arg_tuple.0 = (s32[]{:T(128)}, f32[64,9,144,128]{3,2,1,0:T(8,128)}) parameter(0)
  %get-tuple-element.3338 = f32[64,9,144,128]{3,2,1,0:T(8,128)} get-tuple-element(%arg_tuple.0), index=1
  %get-tuple-element.2935 = s32[64]{0:T(128)} get-tuple-element(%arg_tuple.0), index=0
  %copy.965 = f32[64,2,9216]{2,1,0:T(2,128)S(1)} copy(%get-tuple-element.3338)
  %reshape.1758 = f32[64,144,128]{2,1,0:T(8,128)S(1)} reshape(%copy.965), metadata={op_name="jit(_tick_impl)/vmap(ggrs/burst)/while/body/ggrs/row_layout/reshape" stack_frame_id=70}
  %ring_write.29 = f32[64,9,144,128]{3,2,1,0:T(8,128)} custom-call(%get-tuple-element.2935, %get-tuple-element.2935, %reshape.1758, %get-tuple-element.3338), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[64]{0}, s32[64]{0}, f32[64,144,128]{2,1,0}, f32[64,9,144,128]{3,2,1,0}}, output_to_operand_aliasing={{}: (3, {})}, metadata={op_name="jit(_tick_impl)/vmap(ggrs/burst)/while/body/ggrs/ring_write/pallas_call" stack_frame_id=109}
  ROOT %tuple.474 = (s32[]{:T(128)}, f32[64,9,144,128]{3,2,1,0:T(8,128)}) tuple(%get-tuple-element.2935, %ring_write.29)
}

ENTRY %main.124 (carry: f32[64,9,144,128]) -> f32[64,9,144,128] {
  %carry = f32[64,9,144,128]{3,2,1,0:T(8,128)} parameter(0)
  %tuple.1 = (s32[]{:T(128)}, f32[64,9,144,128]{3,2,1,0:T(8,128)}) tuple(%carry, %carry)
  %while.9 = (s32[]{:T(128)}, f32[64,9,144,128]{3,2,1,0:T(8,128)}) while(%tuple.1), condition=%region_8.1, body=%region_7.94, metadata={op_name="jit(_tick_impl)/vmap(ggrs/burst)/while" stack_frame_id=60}
  ROOT %get-tuple-element.1 = f32[64,9,144,128]{3,2,1,0:T(8,128)} get-tuple-element(%while.9), index=1
}
"""


def test_the_in_place_ring_write_maps_to_the_bursts_ring_write():
    """The kernel that copies a large row in place is one operation of the
    device trace, ``while.9/ring_write.29``: the map gives it the phase and
    the scope that ``phase_burst_ms`` / ``scope_ring_write_ms`` read."""
    rows = {r.name: r for r in xla_cache.op_scopes(_BURST_WITH_THE_KERNEL)}
    kernel = rows["ring_write.29"]
    assert kernel.opcode == "custom-call" and kernel.own
    assert kernel.scopes == ("burst", "ring_write")
    assert kernel.computation == "region_7.94" and not kernel.entry
    assert rows["reshape.1758"].scopes == ("burst", "row_layout")
    # the compiler's own copy serves the operation it feeds
    assert rows["copy.965"].scopes == ("burst", "row_layout")
    assert not rows["copy.965"].own
    assert rows["while.9"].scopes == ("burst",)


def test_the_tools_table_of_device_operations():
    """``tools/trace_spans.py``'s join of a trace's operations with the map:
    every operation with its phase and scopes, a dispatch; the totals."""
    from benchmark.reduce import trace as reduce_trace

    spec = importlib.util.spec_from_file_location(
        "trace_spans", os.path.join(ROOT, "tools", "trace_spans.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    ops = {"while.9": ("burst",), "fusion.1": ("burst", "ring_write"),
           "fusion.4": ("rollout", "schedule", "claim"),
           "fusion.7": ("carry_codec",)}
    trace = reduce_trace.Trace(
        spans=[], blocks={},
        modules={0: [("jit__tick_impl(7)", 1.0, 1.010),
                     ("jit__tick_impl(7)", 2.0, 2.010),
                     ("jit__row_impl(3)", 3.0, 3.001)]},
        op_self_s={0: {"while.9": 0.0002, "while.9/fusion.1": 0.004,
                       "while.10/fusion.4": 0.010, "fusion.7": 0.002,
                       "copy.8": 0.003}})
    table = tool.device_ops_table(trace, ops)
    assert table["dispatches"] == 2
    assert table["tick_program_ms"] == pytest.approx(10.0)
    rows = {r["op"]: r for r in table["device_ops"]}
    assert list(rows) == ["while.10/fusion.4", "while.9/fusion.1", "copy.8",
                          "fusion.7", "while.9"]       # by time, every one
    assert rows["while.10/fusion.4"] == {
        "op": "while.10/fusion.4", "phase": "rollout",
        "scopes": ["schedule", "claim"], "ms": pytest.approx(5.0),
        "share": pytest.approx(0.5)}
    assert rows["copy.8"]["phase"] is None
    assert table["device_phases"] == pytest.approx({
        "burst": 2.1, "rollout": 5.0, "carry_codec": 1.0,
        tool.NO_SCOPE: 1.5, tool.GAPS: 10.0 - 9.6})
    assert table["device_scopes"] == pytest.approx(
        {"ring_write": 2.0, "schedule": 5.0, "claim": 5.0})
    # No such program on a device: nothing, not a table of zeros.
    assert tool.device_ops_table(trace, ops, "^jit__nothing") is None
