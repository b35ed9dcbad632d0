"""Parallelism as axes of one mesh: ("dp", "pp", "fsdp", "ep", "sp", "tp").

mesh.py builds the mesh, sharding.py maps the models' logical axes onto
it, context.py carries the active (mesh, rules) pair to mesh-aware ops,
pipeline.py runs the layer stack over `pp`, distributed.py starts
multi-host gangs.

Layout of activations inside the train step: the batch is split over
(dp, fsdp); heads and the MLP's hidden units over `tp`. With `tp > 1`
the residual stream between the matmuls is also split over `tp`, along
the tokens: norms, residual adds and the layer scan's carry (and what
remat saves of it) hold 1/tp of the tokens. tp_overlap.py gathers the
tokens inside the column-parallel matmuls (wq/wk/wv, w_gate/w_up) and
scatters them again inside the row-parallel ones (wo, w_down), one
token block on the `tp` ring while the MXU multiplies another, so
attention sees whole sequences on its heads' shard and no all-reduce
sits between two ops of a layer.
"""
