"""Expert layer: (token, expert) pairs routed to experts another chip of the deployment holds,
of all pairs (%), from the statistics the train step returns (`pairs_elsewhere`); median of the
traced steps. The rest is the expert load this chip really saw."""

from chipbench import readers_zaya


def read(run):
    pairs = readers_zaya.held_pairs(run)
    return None if pairs is None else 100.0 * (1.0 - pairs["held"] / pairs["all"])
