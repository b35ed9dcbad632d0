"""Runtime and trainer: busy seconds of the program's layer spans `runtime.init`
(ray_tpu.init(), entry to return) and `train.worker_start` (JaxTrainer.fit() entry until the
worker's loop function is entered), from ray_tpu.obs.layer_counters(). The training runner
uses the in-process runtime, so the trainer's worker is a thread of the harness's own
process and its counters are this process's (seen on the CPU and on the chip, PR 24: both
names are present after fit() returns). None where the program has no layer counters."""

from chipbench import readers_setup


def read(run):
    return readers_setup.layer_busy_s(readers_setup.RUNTIME_SPANS)
