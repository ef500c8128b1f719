"""The keys the benchmark passes still construct the program's configuration.

``benchmarks/`` may not be edited by a PR that simplifies or speeds up the
program, so a field it sets cannot be removed or renamed there and here at
once. Each configuration file's ``maps_to`` + ``program`` (+ ``tiny``) block
is put through the constructor exactly as the benchmark's runners do
(``benchmarks/lib/model.py`` ``transformer_config`` over ``_FIELDS``;
``benchmarks/lib/runners/train_causal.py`` over every key, by the class
``program_class`` names), so a PR that drops such a field fails here, in
tier-1, and not on the chip. This is what blocks ROADMAP D11 today: all three
files pass ``attention_impl``, so deriving the attention route from mesh, T
and causality starts with a ``benchmark`` issue that takes it out of them.
The files are read, never edited.
"""
import glob
import json
import os

import pytest

from benchmarks.lib import model
from deeplearning4j_tpu import models

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = sorted(glob.glob(os.path.join(REPO, "benchmarks", "configs",
                                        "*.json")))


@pytest.mark.parametrize("tiny", [False, True], ids=["real", "tiny"])
@pytest.mark.parametrize("path", CONFIGS,
                         ids=lambda p: os.path.basename(p)[:-len(".json")])
def test_program_block_constructs_the_configuration(path, tiny):
    with open(path) as f:
        config = json.load(f)
    sizes = model.sizes(config, tiny)
    if config.get("program_class"):
        cls = getattr(models, config["program_class"])
        cfg = cls(**sizes)
        passed = sizes
    else:
        cls = models.TransformerConfig
        cfg = model.transformer_config(sizes)
        passed = {k: sizes[k] for k in model._FIELDS}
    assert isinstance(cfg, cls)
    # through JSON, so that a tuple field equals the file's list
    assert json.loads(json.dumps({k: getattr(cfg, k) for k in passed})) \
        == passed
    assert models.family_of(cfg) is not None
