"""Learned on-device input prediction (`predict/`): a tiny per-player
input-transition MLP that seeds candidate ranking in the speculative
branch-tree builder.

The tier has three consumers, wired in this order (ROADMAP: "as a third
policy there FIRST"). Each binds the predictor to its configuration's
:class:`~bevy_ggrs_tpu.branch_tree.BranchTree` (the ``predictor`` field)
and hands a ranking it already made to the build as
``structured_bits(seed=)``:

1. the counterfactual replay harness (``obs/ledger.py`` policy
   ``learned``), scored offline against the frozen ``spec_baseline.json``;
2. the live singleton path (``spec_runner.SpeculativeRollbackRunner``
   via ``SessionBuilder.with_input_predictor(...)``), under the full
   determinism contract: versioned content-hashed weights folded into
   the wire handshake, branch 0 stays repeat-last, attestation covers
   predictor-seeded trees;
3. the batched session axis (``serve/batch.py``) where one vmapped
   int8 forward ranks candidates for all S slots per dispatch.

Everything here is **integer-only** on the determinism-stable
int8 x int8 -> int32 dot path proven in ``models/neural_bots.py``: the
numpy host forward and the jitted batched forward are exact integer
programs, so their outputs are bitwise identical on every backend.
"""

from bevy_ggrs_tpu.predict.artifact import (
    DEFAULT_ARTIFACT,
    FORMAT_VERSION,
    PredictorWeights,
    load_artifact,
    load_default,
    save_artifact,
)
from bevy_ggrs_tpu.predict.model import (
    BoundPredictor,
    InputPredictor,
    PredictorSeed,
    resolve_predictor,
    resolve_predictor_config,
)

__all__ = [
    "DEFAULT_ARTIFACT",
    "FORMAT_VERSION",
    "PredictorWeights",
    "load_artifact",
    "load_default",
    "save_artifact",
    "BoundPredictor",
    "InputPredictor",
    "PredictorSeed",
    "resolve_predictor",
    "resolve_predictor_config",
]
