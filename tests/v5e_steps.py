"""The described chip and the train steps built for it: what
tests/test_m7b_steps_compile.py,
tests/test_olmoe_zaya1_keye_steps_compile.py,
tests/test_glm47f_laguna_steps_compile.py and tests/test_tpu_compile.py
share. No test lives here (pytest does not collect the file).

A full-width train step takes one to two minutes to compile for the
described v5e, and the `v5e` fixture turns the persistent compile cache
off, so nothing shares a compile unless the tests do: `train_step` keeps
ONE record a process for one set of arguments, and a record makes its
lowered text, its compiled step and what is read of them once each, on
first request. A test that needs a FRESH trace (an import made to fail, a
rule of models/moe.py patched) builds a `Step` of its own and goes round
the memo. The cells' tests stand two or three cells a file, grouped by
their compiles' seconds, so that `--dist loadfile` gives the compiles to
three workers: one file for all of them was 84% of the lane's wall on
one worker, and a file a cell put seven all-core compiles at once into
the lane's tail, beside the cluster tests whose RPCs time out in seconds
(pytest-xdist starts the files with the most cases first; ROADMAP D8).
Only one process at a time may load the TPU's library unless
`ALLOW_MULTIPLE_LIBTPU_LOAD=1` is set, as the driver's command sets it
(pytest.ini has the command): under several workers without it, every
worker but the first to describe the chip SKIPS its compile files. It is
set in no file of the repository (on-chip-measurement guide, section 2)."""

import functools
import hashlib
import math
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import pytest


@pytest.fixture(scope="module")
def v5e():
    """Devices of a described v5e:2x2, with the persistent compile cache
    off around the module: an entry written for a described chip cannot
    be read back without one, and the next compile would warn. The
    module's records go with it: a file's steps are its own."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    _STEPS.clear()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def one_chip(devices):
    return jax.sharding.SingleDeviceSharding(devices[0])


def compile_kernel(fn, *shapes, sharding):
    """The compiled text of `fn` at (shape, dtype) arguments placed by
    `sharding`: a kernel alone, compiled at every call."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo  # the kernel itself, not a fallback
    return hlo


def train_step_at_mistral_widths(devices, mesh_shape=None, batch=3, *,
                                 model="mistral-7b", n_layers=2, seq=4096, **overrides):
    """(jitted step, abstract state, abstract batch) of a 2-layer
    Mistral-7B-wide train step as chipbench's training cells build it,
    placed on the described devices: one chip, or a 6-axis mesh. With
    `model`, another registry entry's, cut to `n_layers`. Built anew at
    every call: `train_step` is the memo over it."""
    import dataclasses

    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.models.registry import get_model_config
    from ray_tpu.parallel.mesh import MESH_AXES
    from ray_tpu.parallel.sharding import default_rules, tree_shardings
    from ray_tpu.train.step import TrainState, make_train_step

    cfg = dataclasses.replace(get_model_config(model), n_layers=n_layers,
                              attention_impl="flash", **overrides)
    opt = optax.adamw(3e-4)
    params = jax.eval_shape(lambda: llama.init_params(cfg, jax.random.key(0)))
    mesh = rules = None
    if mesh_shape is None:
        one = one_chip(devices)
        param_shardings = jax.tree.map(lambda _: one, params)
        scalar = batch_sharding = one
    else:
        mesh = Mesh(np.asarray(devices).reshape(mesh_shape), MESH_AXES)
        rules = default_rules()
        param_shardings = tree_shardings(mesh, rules, llama.logical_axes(cfg))
        scalar = NamedSharding(mesh, P())
        batch_sharding = NamedSharding(mesh, rules.spec(("batch", "seq")))

    def placed(tree, shardings):
        return jax.tree.map(
            lambda x, s: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=s), tree, shardings)

    params = placed(params, param_shardings)
    opt_state = jax.eval_shape(opt.init, params)
    opt_state = placed(opt_state, optax.tree_map_params(
        opt, lambda _, p: p.sharding, opt_state, params,
        transform_non_params=lambda _: scalar))
    state = TrainState(params=params, opt_state=opt_state,
                       step=jax.ShapeDtypeStruct((), jnp.int32, sharding=scalar))
    tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=batch_sharding)
    loss = llama.loss_fn if model == "mistral-7b" else llama.loss_and_weight_fn
    # what the step asks of the backend when it is built (its compile options) is
    # answered by the described chip, as it would be on one
    with mock.patch("jax.default_backend", return_value="tpu"), \
            mock.patch("jax.devices", return_value=list(devices)):
        step = make_train_step(lambda p, b: loss(p, b, cfg), opt, mesh=mesh, rules=rules)
    return step, state, {"tokens": tokens, "targets": tokens}


class Step:
    """One train step built for the described devices, with what the tests
    read of it, each made once and on first request: the lowered text, the
    compiled step, its text, its memory analysis, and how often each site
    of `obs.layer_counters()` was counted over the lowering and the
    compile. Code that asks `jax.default_backend()` while it is traced
    (flash's interpret switch) is answered "tpu"."""

    def __init__(self, devices, mesh_shape=None, **kwargs):
        self.step, self.state, self.batch = train_step_at_mistral_widths(
            devices, mesh_shape, **kwargs)
        self._counted = {}

    def _counting(self, make):
        from ray_tpu import obs

        before = obs.layer_counters()
        with mock.patch("jax.default_backend", return_value="tpu"):
            made = make()
        for name, entry in obs.layer_counters().items():
            delta = entry["count"] - before.get(name, {"count": 0})["count"]
            self._counted[name] = self._counted.get(name, 0) + delta
        return made

    @functools.cached_property
    def lowered(self):
        return self._counting(lambda: self.step.lower(self.state, self.batch))

    @functools.cached_property
    def lowered_text(self) -> str:
        return self.lowered.as_text()

    @functools.cached_property
    def compiled(self):
        lowered = self.lowered
        return self._counting(lowered.compile)

    @functools.cached_property
    def hlo(self) -> str:
        return self.compiled.as_text()

    @functools.cached_property
    def memory(self):
        return self.compiled.memory_analysis()

    def engaged(self, *names) -> dict:
        """{site: times counted while the step was lowered and compiled}."""
        self.compiled
        return {name: self._counted.get(name, 0) for name in names}

    @functools.cached_property
    def kernels(self) -> list:
        """The names of the compiled step's Pallas kernels, in the text's order."""
        return re.findall(r"%([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"", self.hlo)

    @functools.cached_property
    def computations(self) -> dict:
        """{name: body} of the compiled text's computations."""
        return dict(re.findall(r"^%?([\w.\-]+) \([^\n]*\{\n(.*?)^\}", self.hlo, re.M | re.S))

    @functools.cached_property
    def op_names(self) -> set:
        return set(re.findall(r'op_name="([^"]*)"', self.hlo))

    def has_scope(self, scope: str) -> bool:
        """Whether an operation of the compiled step was traced under the named scope."""
        at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")
        return any(at.search(n) for n in self.op_names)

    def lowered_hash(self) -> str:
        """sha256 of the lowered text, the kernels' serialized bodies taken
        out (they embed source locations)."""
        text = self.lowered_text
        assert "tpu_custom_call" in text
        text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"', 'backend_config = "-"', text)
        return hashlib.sha256(text.encode()).hexdigest()


_STEPS: dict = {}


def train_step(devices, mesh_shape=None, **kwargs) -> Step:
    """The process's one `Step` of these arguments (those of
    `train_step_at_mistral_widths`, as they are spelled: a default written
    out is another record)."""
    key = (mesh_shape, tuple(sorted(kwargs.items())))
    if key not in _STEPS:
        _STEPS[key] = Step(devices, mesh_shape, **kwargs)
    return _STEPS[key]


def matmul_tiles(hlo: str) -> int:
    """The tiles the compiled step's matmul fusions are cut into, summed."""
    return sum(math.prod(int(n) for n in re.findall(r"\d+", bounds))
               for bounds in re.findall(
                   r'kind=k(?:Output|Convolution)[^\n]*"iteration_bounds":\[([^\]]+)\]', hlo))


def grouped_kernels(kernels) -> list:
    """The grouped matmuls among the kernels, their numbering taken off, sorted."""
    return sorted(re.sub(r"\.\d+$", "", k) for k in kernels if k.startswith("ragged-dot"))


def called_from(computations: dict, name: str, seen=None) -> set:
    """The computations `name` runs: itself, its fusions, loops, branches."""
    seen = set() if seen is None else seen
    if name in seen or name not in computations:
        return seen
    seen.add(name)
    body = computations[name]
    called = re.findall(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)", body)
    for group in re.findall(r"branch_computations=\{([^}]*)\}", body):
        called += re.findall(r"%?([\w.\-]+)", group)
    for callee in called:
        called_from(computations, callee, seen)
    return seen
