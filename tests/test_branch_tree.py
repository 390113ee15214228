"""The branch tree has one home (``bevy_ggrs_tpu/branch_tree.py``).

What no other test holds: that the three builders of a tree (the singleton
runner, a served batch, the replay harness) build THE SAME tree from one
configuration; that the tree is a function of its arguments (no state, the
log and the predictor's seed handed in); that nothing below the runner
imports it to get at the tree; and that the boids force paths a name may
select are the two that stayed.
"""

import ast
import dataclasses
import pathlib

import numpy as np
import pytest

from bevy_ggrs_tpu.branch_tree import BranchTree
from bevy_ggrs_tpu.models import boids, box_game
from bevy_ggrs_tpu.native import core as ncore
from bevy_ggrs_tpu.native import spec as native_spec
from bevy_ggrs_tpu.obs import ledger
from bevy_ggrs_tpu.serve.batch import BatchedSessionCore
from bevy_ggrs_tpu.spec_runner import SpeculativeRollbackRunner

PACKAGE = pathlib.Path(__file__).resolve().parent.parent / "bevy_ggrs_tpu"


def _scripted_log(cfg, frames):
    """The replay harness's canonical key cycles, as an as-used log."""
    spec, P, keys = cfg["input_spec"], cfg["players"], cfg["keys"]
    log = {}
    for f in range(frames):
        row = spec.zeros_np(P)
        for h in range(P):
            row[h] = keys[(f // 3 + h) % len(keys)]
        log[f] = row
    return log


def _replay_tree(name, cfg):
    """The tree ``replay_config`` hands a policy, through the registry."""
    seen = []

    def factory(tree, log):
        seen.append(tree)
        return lambda anchor, last, known, mask: (
            np.broadcast_to(last, (1, tree.spec_frames) + last.shape), 1
        )

    ledger.POLICY_REGISTRY["_capture"] = factory
    try:
        ledger.replay_config(name, cfg, 16, policies=("_capture",))
    finally:
        del ledger.POLICY_REGISTRY["_capture"]
    (tree,) = seen
    return tree


@pytest.mark.parametrize("name", list(ledger._replay_configs()))
def test_runner_served_batch_and_replay_build_one_tree(name):
    """``serve/batch.py`` promises a slot's tree is the singleton's "by
    construction": here it is compared, bit for bit, with the harness's."""
    cfg = ledger._replay_configs()[name]
    spec, P = cfg["input_spec"], cfg["players"]
    B, F = cfg["branches"], cfg["spec_frames"]
    # The tree reads the input geometry only: box_game's world stands in.
    world = box_game.make_world(P).commit()
    runner = SpeculativeRollbackRunner(
        box_game.make_schedule(), world, max_prediction=F, num_players=P,
        input_spec=spec, num_branches=B, predictor=False,
    )
    core = BatchedSessionCore(
        box_game.make_schedule(), world, F, P, spec, num_slots=1,
        num_branches=B, predictor=False,
    )
    trees = [runner.tree, core._tree, _replay_tree(name, cfg)]
    assert trees[0] == trees[1] == trees[2]

    log = _scripted_log(cfg, 40)
    anchor = 40
    last = log[anchor - 1]
    zeros = spec.zeros_np(P)
    free = (
        np.broadcast_to(zeros, (F,) + zeros.shape).copy(),
        np.zeros((F, P), dtype=bool),
    )
    # Player 0 confirmed three frames into the span, the last player one.
    known, mask = free[0].copy(), free[1].copy()
    mask[:3, 0] = mask[:1, P - 1] = True
    known[:3, 0] = cfg["keys"][1]
    known[:1, P - 1] = cfg["keys"][2]
    for known_i, mask_i in (free, (known, mask)):
        bits = [
            t.structured_bits(log, last, known_i, mask_i, anchor)
            for t in trees
        ]
        assert bits[0].shape == (B, F, P) and bits[0].dtype == zeros.dtype
        assert np.array_equal(bits[0], bits[1])
        assert np.array_equal(bits[0], bits[2])
        assert np.array_equal(bits[0][:, mask_i], np.broadcast_to(
            known_i[mask_i], (B, int(mask_i.sum()))
        ))
        # The scripted cycles are periodic: the tree is not the trivial one.
        assert len({b.tobytes() for b in bits[0]}) > 1
    prints = {t.history_fingerprint(log, anchor) for t in trees}
    assert len(prints) == 1


def _tree_and_log(branches=16):
    cfg = ledger._replay_configs()["box_game"]
    tree = BranchTree(cfg["input_spec"], 2, branches, 8, tuple(range(16)))
    return tree, _scripted_log(cfg, 30), cfg


def test_equal_arguments_give_equal_trees_whatever_came_between():
    tree, log, _ = _tree_and_log()
    known = np.zeros((8, 2), np.uint8)
    mask = np.zeros((8, 2), bool)
    first = tree.structured_bits(log, log[29], known, mask, 30)
    # Another match's log through the same tree: nothing is remembered.
    other = {f: (row + 1) % 16 for f, row in log.items()}
    assert not np.array_equal(
        tree.structured_bits(other, other[29], known, mask, 30), first
    )
    again = tree.structured_bits(dict(log), log[29].copy(), known.copy(),
                                 mask.copy(), 30)
    assert np.array_equal(first, again)
    assert tree.history_fingerprint(log, 30) == tree.history_fingerprint(
        dict(log), 30
    )
    with pytest.raises(dataclasses.FrozenInstanceError):
        tree.num_branches = 4


@pytest.mark.skipif(not ncore.available(), reason="native core unavailable")
def test_a_dict_and_a_mirrored_log_of_the_same_rows_agree():
    tree, log, cfg = _tree_and_log()
    nat = native_spec.make_spec_builder(
        cfg["input_spec"], 2, 16, 8, tuple(range(16))
    )
    mirrored = native_spec.MirroredLog(nat)
    mirrored.update(log)
    del mirrored[4]
    del log[4]
    known = np.zeros((8, 2), np.uint8)
    mask = np.zeros((8, 2), bool)
    mask[:2, 1] = True
    for anchor in (30, 12, 5):  # the last one anchors on the gap
        last = log.get(anchor - 1, np.zeros(2, np.uint8))
        assert np.array_equal(
            tree.structured_bits(log, last, known, mask, anchor),
            tree.structured_bits(mirrored, last, known, mask, anchor),
        )
        assert tree.history_fingerprint(log, anchor) == (
            tree.history_fingerprint(mirrored, anchor)
        )
        assert np.array_equal(
            tree.candidate_values(log, last)[0],
            tree.candidate_values(mirrored, last)[0],
        )


def test_a_log_shifted_inside_the_window_changes_the_fingerprint():
    tree, _, cfg = _tree_and_log()
    log = _scripted_log(cfg, 100)
    anchor = 100
    base = tree.history_fingerprint(log, anchor)
    # One row rewritten 48 frames back (a rollback's correction): inside.
    inside = dict(log)
    inside[anchor - 48] = (log[anchor - 48] + 1) % 16
    assert tree.history_fingerprint(inside, anchor) != base
    # The same rewrite one frame earlier is outside what the tree reads.
    outside = dict(log)
    outside[anchor - 49] = (log[anchor - 49] + 1) % 16
    assert tree.history_fingerprint(outside, anchor) == base
    # The whole history one frame later: same anchor, another window.
    shifted = {f + 1: row for f, row in log.items() if f + 1 < anchor}
    assert tree.history_fingerprint(shifted, anchor) != base


def test_a_seed_handed_in_is_the_seed_the_predictor_would_give():
    """A tick's seed is an argument: the runner folds one anchor's seed
    into its dedup signature and hands the same one to the build; ``None``
    asks the bound predictor."""
    from bevy_ggrs_tpu.predict import InputPredictor, load_default

    tree, log, _ = _tree_and_log(branches=64)
    bound = InputPredictor(load_default()).bind(
        tree.branch_values, np.uint8, 1
    )
    seeded = dataclasses.replace(tree, predictor=bound)
    known = np.zeros((8, 2), np.uint8)
    mask = np.zeros((8, 2), bool)
    asked = seeded.structured_bits(log, log[29], known, mask, 30)
    seed = bound.seed(log, 30, 8, 2)
    handed = seeded.structured_bits(log, log[29], known, mask, 30, seed=seed)
    assert np.array_equal(asked, handed)
    plain = tree.structured_bits(log, log[29], known, mask, 30)
    assert np.array_equal(asked[0], plain[0])  # branch 0 stays repeat-last
    # Without a predictor a seed is nobody's: the heuristic tree.
    assert np.array_equal(
        tree.structured_bits(log, log[29], known, mask, 30, seed=seed), plain
    )


def _imported_modules(path):
    tree = ast.parse(path.read_text())
    names = set()
    for node in ast.walk(tree):  # function-level imports too
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            module = node.module or ""
            if node.level:  # relative: resolve against the file's package
                parts = path.relative_to(PACKAGE.parent).parts[:-node.level]
                module = ".".join([*parts, *filter(None, [module])])
            names.add(module)
            names.update(f"{module}.{a.name}" for a in node.names)
    return names


def _reaches(names, *modules):
    roots = tuple(f"bevy_ggrs_tpu.{m}" for m in modules)
    return sorted(
        n for n in names
        if any(n == r or n.startswith(r + ".") for r in roots)
    )


ARROWS = {
    "serve_batch": (
        [PACKAGE / "serve" / "batch.py"], ("spec_runner",)),
    "obs": (
        sorted((PACKAGE / "obs").glob("*.py")), ("spec_runner",)),
    "branch_tree": (
        [PACKAGE / "branch_tree.py"],
        ("spec_runner", "serve", "obs", "session"),
    ),
}


@pytest.mark.parametrize("who", list(ARROWS))
def test_nothing_below_the_runner_imports_it_for_the_tree(who):
    files, forbidden = ARROWS[who]
    assert files
    for path in files:
        assert not _reaches(_imported_modules(path), *forbidden), path
    if who == "branch_tree":
        # NumPy, zlib and the standard library: nothing of the package.
        names = _imported_modules(files[0])
        assert not [n for n in names if n.startswith("bevy_ggrs_tpu")]


@pytest.mark.parametrize("kernel", ["pallas", "vpu_tiles"])
def test_an_unknown_force_kernel_is_refused_by_name(kernel):
    with pytest.raises(ValueError) as err:
        boids.make_schedule(kernel=kernel)
    assert kernel in str(err.value)
    assert "'xla'" in str(err.value) and "'mxu'" in str(err.value)


def test_the_sharded_force_has_one_kernel():
    import jax
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("entity",))
    for kernel in ("pallas", "xla"):
        with pytest.raises(ValueError, match="'mxu'"):
            boids.make_sharded_schedule(mesh, kernel=kernel)
    assert boids.make_sharded_schedule(mesh, kernel="mxu") is not None
