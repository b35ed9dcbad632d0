"""Harness: setup_s less the four named phases inside its clock (chipbench/phases.PROGRAM;
the machine's three are outside it) and less setup_runtime_s.train: what stands between them. The harness's argument parsing, manifest
check and plugin files, `make_train_step`, the batch maker, polling for the runtime's
chips. The honesty metric: where it passes a tenth of setup_s, PERF.md says what is in it.
None where the run carries no table of phases."""

from chipbench import readers_setup


def read(run):
    return readers_setup.unnamed_s(run)
