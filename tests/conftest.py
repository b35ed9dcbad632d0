"""Test fixtures.

Tests run on a virtual 8-device CPU mesh (the analog of the reference's
in-process fake clusters, python/ray/cluster_utils.py:135) so SPMD
sharding paths are exercised without TPU hardware.
"""

import os

# Must be set before jax import anywhere in the test process — and must
# OVERRIDE an inherited JAX_PLATFORMS=tpu: cluster tests spawn
# GCS/daemon/worker subprocesses that inherit this environment, and a
# fleet of CPU test workers must never race each other (or a concurrent
# benchmark) for the one real TPU.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# Logical CPU floor for the in-process runtime: local actors are threads,
# so the CPU resource is a concurrency budget, not a core reservation. A
# 1-core CI box must still auto-init enough room for a world_size=2 gang
# (tests that care pass num_cpus explicitly; this only lifts the default).
os.environ.setdefault("RAY_TPU_NUM_CPUS", "8")

# What a CPU test costs is XLA COMPILING its programs, not running them (ROADMAP D8): the
# lane is CPU-bound on compiles of tiny programs. jax's own switch compiles them without
# the optimisation passes (`xla_backend_optimization_level=0`, LLVM's expensive passes
# off): the same programs and the same assertions at about 0.7 of the CPU seconds. Set in
# the environment so that the cluster tests' subprocesses inherit it; `=0` from outside
# gives the optimised lane back. tests/v5e_steps.py turns it off round a module that
# compiles for the described chip: what is read of a TPU compile is the optimised step.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

import shutil  # noqa: E402
import signal  # noqa: E402
import tempfile  # noqa: E402

import jax  # noqa: E402
import pytest  # noqa: E402


# ONE CPU program is compiled ONCE A RUN: the lane compiles the same tiny models' programs
# hundreds of times (every `LLMEngine` a case builds makes its `jax.jit`s anew, so jax's
# in-memory cache never hits across engines, and six workers each compile what the others
# have), so jax's persistent cache stands in one directory a run, made by the process that
# owns the run (pytest-xdist's controller, which hands it to its workers; without xdist the
# one process) and removed when the run ends: no state outlives a run, and a run's seconds do
# not depend on the run before it. Set through `jax.config`, not the environment: the cluster
# tests' subprocesses and chipbench/tools/aa.py's children must not inherit it. The PROGRAM's
# rule is as it was (ray_tpu/utils/compile_cache.py: no CPU cache in a process pinned to the
# CPU, because XLA:CPU reloads its entries with machine-feature complaints on stderr): pytest
# captures a case's stderr. tests/v5e_steps.py's `v5e` fixture turns the cache off round a
# module that compiles for the described chip; a case that counts "compiled" and "loaded"
# (tests/test_obs_layers.py, tests/test_tpu_compile.py) sets a directory of its own.
_LANE_CACHE = pytest.StashKey[str]()


def pytest_configure(config):
    if hasattr(config, "workerinput"):
        path = config.workerinput["lane_compile_cache"]
    else:
        path = config.stash[_LANE_CACHE] = tempfile.mkdtemp(prefix="ray_tpu_lane_xla_")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


@pytest.hookimpl(optionalhook=True)
def pytest_configure_node(node):
    node.workerinput["lane_compile_cache"] = node.config.stash[_LANE_CACHE]


def pytest_unconfigure(config):
    if _LANE_CACHE in config.stash:
        shutil.rmtree(config.stash[_LANE_CACHE], ignore_errors=True)


# A case that waits forever fails ALONE: a lost answer (ROADMAP D8's flaky tail) costs one
# case and five minutes, not the lane's clock. No pytest-timeout here, so the alarm is the
# process's own; a case marked `slow` (a full-width compile takes longer) has none.
CASE_LIMIT_S = 300


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    if item.get_closest_marker("slow"):
        yield
        return

    def out_of_time(signum, frame):
        raise TimeoutError(f"{item.nodeid} was still running after {CASE_LIMIT_S} s "
                           f"(tests/conftest.py: CASE_LIMIT_S)")

    was = signal.signal(signal.SIGALRM, out_of_time)
    signal.setitimer(signal.ITIMER_REAL, CASE_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, was)


# Under pytest-xdist every worker that lets `serve.run` start the HTTP proxy binds its
# default port, and two of them at once collide on 8000 (ROADMAP D8's flaky list:
# test_llm_disagg.py and test_llm_kvtier.py against test_serve.py). No test addresses
# the default port, so each worker has its own.
_worker = os.environ.get("PYTEST_XDIST_WORKER", "")[2:]   # "gw3" -> "3"
if _worker.isdigit():
    from ray_tpu.serve.config import HTTPOptions  # noqa: E402

    assert HTTPOptions.__init__.__defaults__ == ("127.0.0.1", 8000, "")
    HTTPOptions.__init__.__defaults__ = ("127.0.0.1", 8001 + int(_worker), "")


@pytest.fixture(scope="session")
def cpu_devices():
    devs = jax.devices("cpu")
    assert len(devs) == 8, f"expected 8 virtual cpu devices, got {len(devs)}"
    return devs


@pytest.fixture
def backwards_traced():
    """`backwards_traced(run)` -> (fused, split): the flash backwards that
    `run()` traced, by the path they took (tests/test_flash.py,
    tests/test_flash_selection.py: a test imports from no test module)."""
    from ray_tpu import obs

    def counts():
        got = obs.layer_counters()
        return [got.get(n, {"count": 0})["count"] for n in ("flash.bwd_fused", "flash.bwd_split")]

    def traced(run) -> tuple:
        before = counts()
        run()
        return tuple(a - b for a, b in zip(counts(), before))

    return traced


# Cases of tests/chipbench/test_chipbench_zaya.py that spell out the FOUR cells the
# benchmark had when PR 32 wrote them, or a metric's list as that cell's alone. A PR that
# is not a `benchmark` PR may add files under the benchmark's paths (tests/chipbench is
# one) and edit none, so they are skipped from here (that directory's own conftest.py
# does the same for PR 31's cases and may not be edited either), and
# tests/chipbench/test_chipbench_glm_lite.py holds the same properties for however many
# cells there are: the manifest well-formed with one four-chip cell, every start-up
# metric listing every training cell in the manifest's order, the expert layer's
# metrics keeping their first cells. The `benchmark` PR that next edits those tests
# makes them read their lists from BENCHMARK.json and deletes this (ROADMAP S7).
_FOUR_CELLS = {
    "test_manifest_is_well_formed_with_the_cell": None,
    "test_expert_layer_metric_is_reported_by_both_expert_cells": None,
    "test_startup_phase_metric_lists_every_training_cell": None,
    "test_new_metric_is_this_cells_alone_and_moves_train_tok_s": "experts_elsewhere_pct",
}


# PR 37 appended per-layer metrics that every training cell (or the cells of one kind of
# block) reports. Two tests hold a cell's reported set to EXACTLY what it was when the cell
# entered, in one function with everything else they hold of the cell. That function is
# skipped, and tests/chipbench/test_chipbench_step.py carries every other assertion of it
# (chips, generator, `reduced`, `source`, `why`, the configuration file's keys, no "TO FILL",
# one four-chip cell, the cell's place, what it reports and what it never may), for every
# training cell where the manifest can say it: its lists are the manifest's, so a metric
# that joins a cell later skips nothing more (zaya1-train's own such test is among the four
# above, and what those pointed at test_chipbench_glm_lite.py for is held there and here).
_REPORTED_SET_AS_THE_CELL_ENTERED = {
    ("test_chipbench_olmoe.py", "test_manifest_is_well_formed_with_the_cell"),
    ("test_chipbench_glm_lite.py", "test_manifest_is_well_formed_with_the_cell"),
    # PR 40 appended `moe_compact_pct` for the two small shares; laguna-train's own such
    # test is carried, every assertion, by tests/chipbench/test_chipbench_compact.py
    ("test_chipbench_laguna.py", "test_manifest_is_well_formed_with_the_cell"),
}


# One test holds PR 37's metrics to the END of `per_layer`, where a PR has to put what it
# adds: PR 39's six follow them. tests/chipbench/test_chipbench_laguna.py carries the test's
# every assertion with the tail read as "PR 37's, in their order, then what came later".
_TAIL_OF_THE_LIST_AS_PR_37_LEFT_IT = (
    "test_chipbench_step.py", "test_new_metrics_are_appended_and_the_manifest_is_well_formed")


# PR 42 added the seventh cell, `keye-train-8k`, on a second traffic file (8192 tokens), and
# appended it to the lists of the metrics every share cell reports. Three tests spell out what
# was there before it; tests/chipbench/test_chipbench_keye.py carries the assertions of each
# for any number of cells (its `test_a_metrics_cells_stand_in_the_manifests_order...`,
# `test_the_compact_metric_is_the_small_shares...` and `test_every_traffic_file...`):
#  * laguna-train's joined metrics held to a list that ENDS with laguna-train;
#  * `moe_compact_pct` held to exactly the two small shares of PR 40;
#  * every traffic file held to a context of at most 4096.
_SIX_CELLS_AND_ONE_TRAFFIC = {
    ("test_chipbench_laguna.py", "test_joined_metric_keeps_its_entry_and_its_cells_in_their_order"),
    ("test_chipbench_compact.py", "test_metric_is_appended_for_the_two_small_shares"),
    ("test_chipbench_traffic.py",
     "test_every_traffic_file_names_a_generator_and_stays_inside_the_window_of_the_model"),
}


# PR 46 added the eighth cell, `olmo-hybrid-train`, whose full layer has `attn.*` scopes, and
# appended six metrics. ONE test spells out `attn_share_pct`'s list as it ended with
# laguna-train and the tail of `per_layer` as PR 42's six;
# tests/chipbench/test_chipbench_olmo_hybrid.py carries its every assertion under the same
# name, for any number of cells and any tail.
_SEVEN_CELLS_AND_PR_42S_TAIL = (
    "test_chipbench_keye.py",
    "test_every_cell_keeps_what_it_reported_and_the_end_to_end_metrics_are_as_they_were")


# PR 49 added the ninth cell, `twotower-train-8k`, a SECOND cell on the traffic file
# zipf_tokens_8k and a FOURTH small share. Two cases of tests/chipbench/test_chipbench_keye.py
# spell out what was there before it: keye-train-8k as the ONE cell on that traffic (the last
# line of its manifest test) and `moe_compact_pct`'s list as ENDING with it.
# tests/chipbench/test_chipbench_nemotron_h.py carries every assertion of both for any number
# of cells (`test_keyes_cell_is_as_it_entered_but_no_longer_alone_on_its_traffic`, and its own
# `test_joined_metric_keeps_its_entry_and_its_cells_in_their_order[moe_compact_pct]`).
_EIGHT_CELLS_AND_ONE_CELL_ON_THE_8K_TRAFFIC = {
    ("test_chipbench_keye.py", "test_manifest_is_well_formed_with_the_cell"): None,
    ("test_chipbench_keye.py",
     "test_joined_metric_keeps_its_entry_and_its_cells_in_their_order"): "moe_compact_pct",
}


# PR 51 appended eight per-layer metrics of the step's HOST timeline that all nine training
# cells report. Three tests of the two newest cells hold the cell's reported set to exactly
# what it entered with, or the END of `per_layer` to that cell's own metrics (nine cases).
# tests/chipbench/test_chipbench_step_timeline.py runs each of them, every assertion, on the
# manifest less what PR 51 appended (`test_an_earlier_cells_test_holds_on_the_manifest_...`),
# and holds the eight to the end of the list itself.
_THE_SETS_AND_THE_TAIL_BEFORE_PR_51 = {
    ("test_chipbench_nemotron_h.py", "test_manifest_is_well_formed_with_the_cell"),
    ("test_chipbench_nemotron_h.py", "test_new_metric_is_this_cells_alone_and_moves_train_tok_s"),
    ("test_chipbench_olmo_hybrid.py", "test_manifest_is_well_formed_with_the_cell"),
}


# PR 53 added the tenth cell, `mellum2-train-16k`: a SECOND cell whose sliding layers have
# `swa.*` scopes, a third traffic file (16,384 tokens), and five metrics after PR 51's eight.
# Each case below spells out what was there before it, and
# tests/chipbench/test_chipbench_mellum2.py carries its every assertion (the test as its PR
# wrote it, run on the manifest less what PR 53 appended) under the name given here:
#  * `swa_share_pct` as laguna-train's ALONE (two tests):
#    `test_lagunas_window_metric_is_as_it_entered_with_a_second_cell_after_it`,
#    `test_every_cell_keeps_what_it_reported_and_the_end_to_end_metrics_are_as_they_were`;
#  * every traffic file within 8,192 tokens:
#    `test_every_traffic_file_names_a_generator_and_stays_inside_the_window_of_its_models`;
#  * `attn_share_pct`'s list ENDING with twotower-train-8k:
#    `test_the_attention_familys_list_keeps_twotower_and_gains_this_cell`;
#  * PR 51's eight as the END of `per_layer`, listing NINE cells:
#    `test_the_host_timelines_eight_are_reported_by_every_training_cell`;
#  * twotower-train-8k's seven as the end of the list once PR 51's eight are taken off (seven
#    cases): `test_twotowers_new_metric_is_as_it_entered_on_the_manifest_less_what_came_later`.
_NINE_CELLS_ONE_WINDOW_CELL_AND_8K_OF_TRAFFIC = {
    ("test_chipbench_laguna.py",
     "test_new_metric_is_this_cells_alone_and_moves_train_tok_s"): ("name", "swa_share_pct"),
    ("test_chipbench_olmo_hybrid.py",
     "test_every_cell_keeps_what_it_reported_and_the_end_to_end_metrics_are_as_they_were"): None,
    ("test_chipbench_keye.py",
     "test_every_traffic_file_names_a_generator_and_stays_inside_the_window_of_its_models"): None,
    ("test_chipbench_nemotron_h.py",
     "test_joined_metric_keeps_its_entry_and_its_cells_in_their_order"): ("name", "attn_share_pct"),
    ("test_chipbench_step_timeline.py",
     "test_the_eight_are_appended_for_all_nine_cells_and_the_manifest_has_no_problems"): None,
    ("test_chipbench_step_timeline.py",
     "test_an_earlier_cells_test_holds_on_the_manifest_less_what_pr_51_appended"):
        ("test", "test_new_metric_is_this_cells_alone_and_moves_train_tok_s"),
}


# PR 55 added the eleventh cell, `sdar-train-8k`, and seven metrics after PR 53's five. Each
# case of tests/chipbench/test_chipbench_mellum2.py below takes PR 53's entries off the
# manifest and holds what is left to the nine-cell benchmark (or finds PR 53's five at the end
# of `per_layer`). That file is the accepted benchmark's (`paths` of BENCHMARK.json) and a PR
# may not edit it, so the cases cannot be rewritten to read the manifest of their own day:
# they are skipped here and tests/chipbench/test_chipbench_sdar.py runs each, every assertion
# and every parameter, on the manifest less what PR 55 appended
# (`test_mellum2s_test_holds_on_the_manifest_less_what_pr_55_appended`). That file's own
# cases read the manifest AS PR 55 LEFT IT (`as_this_pr_left_it`), so the next cell needs no
# third list here.
_TEN_CELLS_AND_PR_53S_TAIL = {
    ("test_chipbench_mellum2.py", "test_nothing_the_parent_had_is_changed_but_by_the_cell_appended"),
    ("test_chipbench_mellum2.py", "test_joined_metric_keeps_its_entry_and_its_cells_in_their_order"),
    ("test_chipbench_mellum2.py",
     "test_the_attention_familys_list_keeps_twotower_and_gains_this_cell"),
    ("test_chipbench_mellum2.py",
     "test_the_host_timelines_eight_are_reported_by_every_training_cell"),
    ("test_chipbench_mellum2.py",
     "test_twotowers_new_metric_is_as_it_entered_on_the_manifest_less_what_came_later"),
}


# PR 66 added the fourteenth cell, `granite-h-micro-train-packed`, the FIRST cell on a second
# generator (generators/packed_zipf_docs.py: documents packed end to end). One case of
# tests/chipbench/test_chipbench_step.py holds every training cell to the one generator there
# was, `zipf_tokens`, in one function with everything else it holds of a cell; that file is the
# accepted benchmark's and may not be edited, so the new cell's case is skipped here and
# tests/chipbench/test_chipbench_granite_hybrid.py carries its every other assertion for that
# cell (`test_manifest_is_well_formed_with_the_cell`: chips, `reduced`, `source`, `why`, the
# configuration file's keys, no "TO FILL", what it reports, the two end-to-end metrics). Every
# OTHER cell's case runs as it did.
_ONE_GENERATOR = {
    ("test_chipbench_step.py",
     "test_training_cell_is_well_formed_and_reports_what_it_did_and_the_new_metrics"):
        ("name", "granite-h-micro-train-packed"),
}


# The one case of the lane that holds what a JITTED program computes to what the same
# functions give taken bare, BIT FOR BIT (a runner's parameters, initialised inside its
# program, against `init_params` called operation by operation): the unoptimised CPU programs
# of this lane (the top of this file) round the two differently. It may not be edited from a
# PR that is no `benchmark` PR, so it is run with the optimisation passes, from here.
_BIT_FOR_BIT_ACROSS_TWO_PROGRAMS = {
    ("test_chipbench_olmo_hybrid.py",
     "test_the_program_gradient_is_the_train_steps_own_and_meets_the_references"),
}


@pytest.fixture(autouse=True)
def _optimised_where_two_programs_are_held_bit_for_bit(request):
    case = (os.path.basename(str(request.node.fspath)), getattr(request.node, "originalname", None))
    if case not in _BIT_FOR_BIT_ACROSS_TWO_PROGRAMS:
        yield
        return
    was = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    # the side taken bare runs operation by operation, and an operation's executable is kept a
    # PROCESS: one that an earlier file of this worker compiled without the passes (a tiny
    # model's [512, 64] tables have the same shapes in every model's tests) would be used again
    # here, whatever the flag says now (PR 55: a new file moved pytest-xdist's order, and the
    # case failed in the lane where it passed alone)
    jax.clear_caches()
    yield
    jax.config.update("jax_disable_most_optimizations", was)


def pytest_collection_modifyitems(items):
    for item in items:
        name = getattr(item, "originalname", None)
        file = os.path.basename(str(item.fspath))
        if (file, name) in _ONE_GENERATOR:
            only = _ONE_GENERATOR[(file, name)]
            if item.callspec.params.get(only[0]) == only[1]:
                item.add_marker(pytest.mark.skip(
                    reason="holds every training cell to the generator zipf_tokens, as before "
                           "PR 66; test_chipbench_granite_hybrid.py carries its other assertions "
                           "for the cell on packed documents"))
                continue
        if (file, name) in _TEN_CELLS_AND_PR_53S_TAIL:
            item.add_marker(pytest.mark.skip(
                reason="spells out ten cells or per_layer ending with PR 53's five, as before "
                       "PR 55; test_chipbench_sdar.py runs it on the manifest less what PR 55 "
                       "appended"))
            continue
        if (file, name) in _NINE_CELLS_ONE_WINDOW_CELL_AND_8K_OF_TRAFFIC:
            only = _NINE_CELLS_ONE_WINDOW_CELL_AND_8K_OF_TRAFFIC[(file, name)]
            if only is None or item.callspec.params.get(only[0]) == only[1]:
                item.add_marker(pytest.mark.skip(
                    reason="spells out nine cells, one cell with a window, traffic of at most "
                           "8,192 tokens or the end of per_layer, as before PR 53; "
                           "test_chipbench_mellum2.py carries its assertions for any number"))
                continue
        if (file, name) in _THE_SETS_AND_THE_TAIL_BEFORE_PR_51:
            item.add_marker(pytest.mark.skip(
                reason="holds the cell's reported set, or the end of per_layer, to what it was "
                       "before PR 51 appended the host timeline's metrics; "
                       "test_chipbench_step_timeline.py runs it on the manifest less those"))
            continue
        if (file, name) in _EIGHT_CELLS_AND_ONE_CELL_ON_THE_8K_TRAFFIC:
            only = _EIGHT_CELLS_AND_ONE_CELL_ON_THE_8K_TRAFFIC[(file, name)]
            if only is None or item.callspec.params.get("name") == only:
                item.add_marker(pytest.mark.skip(
                    reason="spells out keye-train-8k as the one cell on zipf_tokens_8k (or the "
                           "last of the small shares), as before PR 49; "
                           "test_chipbench_nemotron_h.py carries its assertions for any number"))
                continue
        if (file, name) == _SEVEN_CELLS_AND_PR_42S_TAIL:
            item.add_marker(pytest.mark.skip(
                reason="spells out a list that ends with laguna-train and the tail PR 42 left; "
                       "test_chipbench_olmo_hybrid.py carries its assertions for any number"))
            continue
        if (file, name) in _SIX_CELLS_AND_ONE_TRAFFIC:
            item.add_marker(pytest.mark.skip(
                reason="spells out the six cells (or the one traffic file) there were before "
                       "PR 42; test_chipbench_keye.py carries its assertions for any number"))
            continue
        if (file, name) == _TAIL_OF_THE_LIST_AS_PR_37_LEFT_IT:
            item.add_marker(pytest.mark.skip(
                reason="holds PR 37's metrics to the end of per_layer, where later PRs append; "
                       "test_chipbench_laguna.py carries its assertions for any tail"))
            continue
        if (file, name) in _REPORTED_SET_AS_THE_CELL_ENTERED:
            item.add_marker(pytest.mark.skip(
                reason="holds the cell's reported metrics to exactly the set it entered with; "
                       "test_chipbench_step.py (laguna-train's: test_chipbench_compact.py) "
                       "carries every other assertion of it"))
            continue
        if file != "test_chipbench_zaya.py" or name not in _FOUR_CELLS:
            continue
        only = _FOUR_CELLS[name]
        if only is None or item.callspec.params.get("name") == only:
            item.add_marker(pytest.mark.skip(
                reason="spells out the four cells of PR 32 (or a metric's list as zaya1-train's "
                       "alone); test_chipbench_glm_lite.py holds the same for any number of cells"))
