"""The described chip and the train steps built for it: what the seven
step-compile files (tests/test_m7b_steps_compile.py,
tests/test_zaya1_keye_steps_compile.py,
tests/test_glm47f_laguna_steps_compile.py,
tests/test_olmo_hybrid_twotower_steps_compile.py,
tests/test_solar_open2_step_compile.py,
tests/test_kimi_linear_step_compile.py,
tests/test_granite_hybrid_step_compile.py) and tests/test_tpu_compile.py
share. No test lives here (pytest does not collect the file).

A full-width train step takes 30-80 s alone to compile for the described
v5e (several times that beside five other workers) and 1-9 s to lower, and the `v5e` fixture turns the
persistent compile cache off, so THE TIER-1 LANE COMPILES NO STEP (PR 68;
tests/test_step_files_layout.py holds the files to it): a case of the lane
reads the LOWERED module, and what only a compile shows is one case a step
marked `slow`. The lowered module says which kernels stand at how many
sites under which scope at which shapes (`lowered_kernels`), which name
stacks its operations carry (`lowered_op_names`, `has_scope(..,
lowered=True)`), that XLA's own ragged dot is not there, how often a site
was traced (`engaged`, which the TRACE counts), what the step asks of the
compiler (`compiler_options`), the text's hash, and the bytes of the
step's arguments (`argument_bytes`, summed from its abstract inputs). A
COMPILE shows the temporaries' bytes and that arguments + temporaries fit
the chip, the VMEM an operation is given, the tiles of a fusion, a layout,
a copy or a transpose, `.remat`, a branch's own computations, a transfer
started before a matmul and done after it, the kernels' names in the
compiled text, and which scopes outlive XLA's fusion (`scopes_lost`).
Those are also what every PR's run of the cell on the chip shows
(`hbm_step_gib.train`, `hbm_peak_gib.train`, the step's table by scope,
`step_unscoped_pct`): a builder who edits ray_tpu/ops/, ray_tpu/models/ or
ray_tpu/train/ runs `python -m pytest -m slow tests/test_<cell>_step(s)_compile.py`
for every cell whose step the edit moves, or that cell on the chip
(ROADMAP D8, rule (a)).

`train_step` keeps ONE record a process for one set of arguments, and a
record makes its lowered text, its compiled step and what is read of them
once each, on first request: a file's lane cases share one lowering, its
slow cases one compile. A test that needs a FRESH trace (an import made to
fail, a rule of models/moe.py patched) builds a `Step` of its own and goes
round the memo. Laguna-S-2.1's step is compiled at two of the cell's five
layers and Keye-VL-2.0's at one of its two (what is read of those compiles
holds at either depth).
Only one process at a time may load the TPU's library unless
`ALLOW_MULTIPLE_LIBTPU_LOAD=1` is set, as the driver's command sets it
(pytest.ini has the command): under several workers without it, every
worker but the first to describe the chip SKIPS its step files. It is
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
    be read back without one, and the next compile would warn. XLA's
    optimisation passes, which tests/conftest.py turns off for the lane's
    CPU programs, are ON around the module: what is read of a compile for
    the chip is the optimised step. The module's records go with it: a
    file's steps are its own."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or libtpu logs under /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    was = jax.config.jax_enable_compilation_cache, jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_disable_most_optimizations", False)
    compilation_cache.reset_cache()
    yield topo.devices
    _STEPS.clear()
    jax.config.update("jax_enable_compilation_cache", was[0])
    jax.config.update("jax_disable_most_optimizations", was[1])
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
    of `obs.layer_counters()` was counted over the lowering (a compile
    runs none of the program's Python). Code that asks
    `jax.default_backend()` while it is traced (flash's interpret switch)
    is answered "tpu"."""

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
        with mock.patch("jax.default_backend", return_value="tpu"):
            return lowered.compile()

    @functools.cached_property
    def hlo(self) -> str:
        return self.compiled.as_text()

    @functools.cached_property
    def memory(self):
        memory = self.compiled.memory_analysis()
        # the lane reads `argument_bytes` in this number's place: wherever a step is compiled,
        # the two are held to each other
        assert abs(memory.argument_size_in_bytes - self.argument_bytes) < 2 ** 20
        return memory

    @functools.cached_property
    def argument_bytes(self) -> int:
        """What one device holds of the step's arguments (state and batch),
        summed from its abstract inputs: `memory.argument_size_in_bytes`
        without a compile (`memory` holds the two to each other)."""
        return sum(math.prod(x.sharding.shard_shape(x.shape)) * x.dtype.itemsize
                   for x in jax.tree.leaves((self.state, self.batch)))

    @property
    def compiler_options(self) -> dict:
        """What the step asks of the compiler when it is compiled (train/step.py's
        VMEM limit), as the LOWERED step carries it."""
        return dict(self.lowered._lowering._compiler_options_kvs)

    def engaged(self, *names) -> dict:
        """{site: times counted while the step was traced}: the lowering
        counts them, a compile runs no Python of the program's."""
        self.lowered
        return {name: self._counted.get(name, 0) for name in names}

    @functools.cached_property
    def _lowered_locations(self) -> tuple:
        """(the lowered text with its locations, {location: the name stack it was traced under})."""
        text = self.lowered.as_text(debug_info=True)
        return text, dict(re.findall(r'^#loc(\d+) = loc\("([^"]*)"', text, re.M))

    @functools.cached_property
    def lowered_op_names(self) -> set:
        """The name stacks of the LOWERED module's operations: `op_names` without a compile."""
        return set(self._lowered_locations[1].values())

    @functools.cached_property
    def lowered_kernels(self) -> list:
        """The Pallas kernels of the LOWERED module by the names the compiled
        step gives them, a site each: a kernel called where it stands is
        named after the scope it was traced under (`mla.attend`), one inside
        a jitted function of its own after that function, at every call of
        it (`ragged-dot-tiled`, the module's numbering taken off)."""
        text, names = self._lowered_locations
        functions = re.split(r"^  func\.func ", text, flags=re.M)[1:]
        at = r'custom_call @tpu_custom_call\([^\n]*loc\(#loc(\d+)\)$'
        # every kernel's location is a plain `#locN = loc("name stack"...)` line: another
        # form of it (a callsite, a fused location) is jax's printer changed, not a kernel gone
        assert text.count("@tpu_custom_call(") == len(re.findall(at, text, re.M)), \
            "a tpu_custom_call whose line does not end in loc(#locN)"
        unnamed = {n: re.findall(rf"^#loc{n} = .*$", text, re.M)
                   for n in re.findall(at, text, re.M) if n not in names}
        assert not unnamed, f"kernel locations that are no loc(\"name\"...): {unnamed}"
        own = {re.match(r'\w+ @"?([\w.\-]+)"?\(', f).group(1) for f in functions
               if any(names[at_] == "pallas_call" for at_ in re.findall(at, f, re.M))}
        sites = []
        for site in re.finditer(at + r'|call @"?([\w.\-]+)"?\(', text, re.M):
            if site.group(2) in own:
                sites.append(re.sub(r"_\d+$", "", site.group(2)))
            elif site.group(1) and names[site.group(1)] != "pallas_call":
                sites.append(names[site.group(1)].split("/")[-2])
        return sites

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

    def has_scope(self, scope: str, lowered=False) -> bool:
        """Whether an operation of the compiled step (`lowered`: of the
        lowered module) was traced under the named scope."""
        at = re.compile(r"(?:^|[/(])" + re.escape(scope) + r"(?:[/)]|$)")
        return any(at.search(n) for n in (self.lowered_op_names if lowered else self.op_names))

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


def scopes_lost(step: Step, scopes) -> list:
    """The scopes among `scopes` that no operation of the COMPILED step was
    traced under: XLA fused their operations into another's or eliminated
    them, and a trace's readers find nothing."""
    return [scope for scope in scopes if not step.has_scope(scope)]


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
