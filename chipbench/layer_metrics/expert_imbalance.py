"""Expert layer: tokens the busiest expert received over the mean, from the statistics the train
step returns (metrics["stats"], reported every step); median of the traced steps, the largest
layer of each. 1.0 is an even split; uneven groups are what the grouped matmuls meet."""

from chipbench import readers_moe


def read(run):
    return readers_moe.expert_imbalance(run)
