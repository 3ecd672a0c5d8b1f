"""The step accounts for itself (PR 49): every equation of a train step's
jaxpr runs under a scope of ``telemetry.STEP_SCOPES``, every scope the
program names is in that registry and is entered by some configuration,
the scopes changed names only (the steps trace to the parent's programs),
and the benchmark's reader bills each device operation once, to the
innermost scope on its name."""

import ast
import functools
import hashlib
import importlib
import json
import os
import re

import jax
import jax.numpy as jnp
import optax
import pytest

from chipbench import xplane
from chipbench.readers import step_scopes
from ray_shuffling_data_loader_tpu.runtime import telemetry

PACKAGE = "ray_shuffling_data_loader_tpu"
DECODERS = ("mellum_tiny", "laguna_tiny", "granite_tiny", "phi4flash_tiny",
            "lfm2_tiny", "sdar_tiny")
CONFIGURATIONS = DECODERS + ("dlrm", "bert", "dlrm_under_a_mesh")
SCOPE_LIKE = re.compile(r"rsdl\.[\w.]*\w")

#: What holds a model's operations outside its named parts: element-wise
#: passes, shapes and scalars, never one of ``HEAVY``. A layer added to a
#: decoder without a scope of its own lands its products, kernels and loops
#: here and turns its configuration's case red.
CATCH_ALLS = ("rsdl.lm.layer", "rsdl.lm.loss", "rsdl.lm.moe_loops")
HEAVY = frozenset({"dot_general", "conv_general_dilated", "pallas_call",
                   "sort", "top_k", "gather", "scatter", "scatter-add",
                   "cumsum", "cumlogsumexp", "custom_call"})


def _no_scope_is_fine(stack, eqn):
    """The allow-list: the reason an equation may run under no scope."""
    scalar = all(v.aval.shape == () for v in eqn.outvars)
    # a ``fori_loop``'s own counter and test in the masked-LM head's walk:
    # its ``while`` carries no scope of the program's, so that a reader
    # which sums ``rsdl.bert.mlm_head`` counts no loop beside its body
    if (eqn.primitive.name in ("add", "lt") and scalar
            and re.search(r"jit\(_masked_nll_(fwd|bwd)\)$", stack)):
        return True
    # autodiff's zero for a leaf no gradient reaches (a router that does
    # not train, a selection bias) and, under a mesh, its re-placing of a
    # cotangent it hands back: made at the step's top level
    return eqn.primitive.name in ("broadcast_in_dim", "reshard") \
        and stack == ""


def _step_and_arguments(name):
    """``(train step, its abstract arguments)`` of a configuration at its
    tiny sizes: ``make_train_step`` over the model's loss and Adam."""
    from ray_shuffling_data_loader_tpu.parallel import trainer
    optimizer = optax.adam(1e-4)
    key = jax.eval_shape(lambda: jax.random.key(0))
    tokens = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    if name.startswith("dlrm"):
        from ray_shuffling_data_loader_tpu.models import dlrm
        mesh = None
        config = dlrm.DLRMConfig(
            vocab_sizes=tuple(min(v, 1000)
                              for v in dlrm.DATA_SPEC_VOCAB_SIZES),
            embed_dim=8, top_hidden=(64, 32), compute_dtype=jnp.float32)
        if name == "dlrm_under_a_mesh":
            # the Pallas lookup once a shard, its backward the exchange
            from ray_shuffling_data_loader_tpu.parallel import (
                mesh as mesh_mod)
            mesh = mesh_mod.make_mesh(devices=jax.devices()[:4])
            config = dlrm.DLRMConfig(
                vocab_sizes=(64, 3000), embed_dim=8, top_hidden=(16,),
                compute_dtype=jnp.float32, lookup_mode="pallas")
        params = jax.eval_shape(lambda k: dlrm.init(config, k),
                                jax.random.key(0))

        def loss(p, columns, labels):
            return dlrm.loss_fn(config, p, None, columns, labels, mesh)

        batch = ([jax.ShapeDtypeStruct((16,), jnp.int32)]
                 * config.num_sparse,
                 jax.ShapeDtypeStruct((16,), jnp.float32))
    elif name == "bert":
        from ray_shuffling_data_loader_tpu.models import bert
        from ray_shuffling_data_loader_tpu.workloads import bert_mlm
        config = bert.bert_tiny()
        params = jax.eval_shape(lambda k: bert.init(config, k),
                                jax.random.key(0))

        def loss(p, ids, mask_key):
            inputs, targets = bert_mlm.mlm_mask(ids, mask_key,
                                                config.vocab_size)
            return bert.loss_fn(config, p, inputs, targets)

        batch = (tokens, key)
    else:
        from ray_shuffling_data_loader_tpu.models import mellum
        config = getattr(mellum, name)()
        params = jax.eval_shape(lambda k: mellum.init(config, k),
                                jax.random.key(0))
        if config.diffusion_block:
            def loss(p, ids, noise_key):
                return mellum.loss_fn(config, p, ids, None, noise_key)

            batch = (tokens, key)
        else:
            loss = functools.partial(mellum.loss_fn, config)
            batch = (tokens,)
    return (trainer.make_train_step(loss, optimizer),
            (params, jax.eval_shape(optimizer.init, params), *batch))


@functools.lru_cache(maxsize=None)
def _traced(name):
    """The configuration's step as ``jax.make_jaxpr`` has it."""
    step, arguments = _step_and_arguments(name)
    jax.clear_caches()
    return jax.make_jaxpr(step)(*arguments)


def _leaves(jaxpr, prefix=""):
    """``(name stack, equation)`` of every equation that holds no jaxpr of
    its own, a ``pjit``'s, a ``custom_vjp``'s, a checkpoint's, a ``while``'s
    and a ``cond``'s bodies walked into: an inner equation's stack is
    relative to the equation that holds it."""
    for eqn in jaxpr.eqns:
        stack = "/".join(part for part in (
            prefix, str(eqn.source_info.name_stack)) if part)
        inner = list(jax.core.jaxprs_in_params(eqn.params))
        if not inner:
            yield stack, eqn
            continue
        if eqn.primitive.name in ("pjit", "jit"):
            stack = "/".join(part for part in (
                stack, f"jit({eqn.params.get('name')})") if part)
        for body in inner:
            yield from _leaves(body, stack)


# -- (a) every equation of a step under a registered scope --------------------


@pytest.mark.parametrize("name", CONFIGURATIONS)
def test_every_equation_of_the_step_runs_under_a_registered_scope(name):
    bare, unregistered, misplaced = [], set(), []
    count = 0
    for stack, eqn in _leaves(_traced(name).jaxpr):
        count += 1
        scopes = SCOPE_LIKE.findall(stack)
        unregistered.update(s for s in scopes
                            if s not in telemetry.STEP_SCOPES)
        if not scopes:
            if not _no_scope_is_fine(stack, eqn):
                bare.append((stack, eqn.primitive.name))
        elif scopes[-1] in CATCH_ALLS and eqn.primitive.name in HEAVY:
            misplaced.append((scopes[-1], eqn.primitive.name))
    assert count > 300      # the walk went into the step
    assert not unregistered
    assert not bare, f"{len(bare)} equations under no scope: {bare[:10]}"
    assert not misplaced, misplaced[:10]


# -- (b) one registry ---------------------------------------------------------


def _program_modules():
    root = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), PACKAGE)
    for folder in ("ops", "models"):
        for file in sorted(os.listdir(os.path.join(root, folder))):
            if file.endswith(".py") and file != "__init__.py":
                yield os.path.join(root, folder, file), (
                    f"{PACKAGE}.{folder}.{file[:-3]}")
    yield (os.path.join(root, "parallel", "trainer.py"),
           f"{PACKAGE}.parallel.trainer")
    yield (os.path.join(root, "workloads", "bert_mlm.py"),
           f"{PACKAGE}.workloads.bert_mlm")


def test_every_scope_the_program_names_is_in_the_registry():
    """A module's ``*SCOPE`` constants are keys of ``STEP_SCOPES``, under
    the module the registry says enters them (or one that hands the name
    on: ``models/mellum.py``'s ``MOE_SCOPE = moe.SCOPE``), and no
    ``jax.named_scope`` takes a name written out at the call."""
    constants = {}
    for path, module_name in _program_modules():
        with open(path) as f:
            tree = ast.parse(f.read())
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and getattr(node.func, "attr", "") == "named_scope"):
                assert not isinstance(node.args[0], ast.Constant), (
                    path, node.lineno)
        module = importlib.import_module(module_name)
        for node in tree.body:
            if not isinstance(node, ast.Assign):
                continue
            for target in node.targets:
                if isinstance(target, ast.Name) and target.id.endswith(
                        "SCOPE"):
                    value = getattr(module, target.id)
                    assert value in telemetry.STEP_SCOPES, (path, target.id)
                    constants.setdefault(value, set()).add(
                        module_name.split(".", 1)[1].replace(".", "/")
                        + ".py")
    assert set(constants) == set(telemetry.STEP_SCOPES)
    for scope, (layer, module) in telemetry.STEP_SCOPES.items():
        assert module in constants[scope], scope
        assert layer in ("trainer", "model", "kernels", "collectives")
    with pytest.raises(KeyError):
        telemetry.step_scope("rsdl.lm.nothing")


def test_every_registered_scope_is_entered_by_some_configurations_step():
    entered = set()
    for name in CONFIGURATIONS:
        for stack, _ in _leaves(_traced(name).jaxpr):
            entered.update(SCOPE_LIKE.findall(stack))
    assert entered == set(telemetry.STEP_SCOPES)


# -- the programs are the parent's --------------------------------------------

# sha256 (first 16 hex digits) of ``str(jax.make_jaxpr(step)(...))``,
# addresses and source paths taken out (``tests/test_sdar.py``'s way), of
# each configuration's step at PR 48's commit (1fb9d5d): a scope is a name
# on an equation and the printed jaxpr holds none, so a digest that moves
# says a computation did. A PR that means to change a program puts the new
# digest here.
_PARENT_PROGRAMS = {
    "mellum_tiny": "902ed7b410107205",
    "laguna_tiny": "bef26c60b4c8392f",
    "granite_tiny": "35c4d69d13bfdc3f",
    "phi4flash_tiny": "f7044936b57f8fe2",
    "lfm2_tiny": "65554b596c490cce",
    "sdar_tiny": "73a5b9c8f0e80e2d",
    "dlrm": "23070169d06e4cde",
    "bert": "fb469af455f44296",
}


# (the lookup under a mesh prints its ``shard_map``s' variables by what was
# traced before it: no digest of it holds from one order of tests to another)
@pytest.mark.parametrize("name", sorted(_PARENT_PROGRAMS))
def test_the_scopes_changed_no_program(name):
    text = re.sub(r" at (/root/\S+|0x[0-9a-f]+)", "", str(_traced(name)))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_PROGRAMS[name]


# -- (c) the benchmark's reader -----------------------------------------------

_MODULE = "^jit_train_step$"


def _op(name, opcode, start, end):
    return xplane.Op(name, opcode, f"%{name} = f32[8]{{0}} {opcode}(%x)",
                     start, end)


def _facts(ops, names, chips=1):
    run = xplane.Op("jit_train_step", "module", "jit_train_step(1)", 0.0,
                    1.0)
    trace = xplane.Trace(ops={chip: list(ops) for chip in range(chips)},
                         modules={chip: [run] for chip in range(chips)},
                         spans=[])
    return {"trace": trace, "trace_window": (0.0, 1.0),
            "step_op_names": names, "step_module": _MODULE}


_OPS = [_op("fusion.1", "fusion", 0.0, 0.4), _op("fusion.2", "fusion", 0.4,
                                                 0.6),
        _op("while.3", "while", 0.0, 0.9), _op("copy-done.4", "copy-done",
                                               0.6, 0.65),
        _op("add.5", "add", 0.65, 0.8), _op("fusion.6", "fusion", 0.8, 0.9)]
_NAMES = {
    # a scope inside a scope: the innermost is billed
    "fusion.1": "jit(train_step)/jvp(rsdl.lm.layer)/jit(_project)/"
                "rsdl.lm.proj/dot_general",
    # a scope entered outside a jit, inside the transforms' names
    "fusion.2": "jit(train_step)/transpose(jvp(rsdl.lm.norm))/mul",
    # a container: its body's operations are beside it
    "while.3": "jit(train_step)/jvp(rsdl.lm.layer)/rsdl.lm.moe_loops/"
               "jit(_moe_fwd)/while",
    "add.5": "jit(train_step)/jvp()/jit(_threefry_fold_in)/add",
    "fusion.6": "jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
                "rsdl.lm.layer/add_any",
}


def test_each_operation_is_billed_once_to_its_innermost_scope():
    assert step_scopes.billed_to(_NAMES["fusion.1"]) == "rsdl.lm.proj"
    assert step_scopes.billed_to(_NAMES["fusion.2"]) == "rsdl.lm.norm"
    assert step_scopes.billed_to(_NAMES["add.5"]) == step_scopes.NAMED
    assert step_scopes.billed_to("") == step_scopes.UNNAMED
    rows = step_scopes.billed_seconds(_OPS, _NAMES)
    assert rows == pytest.approx({
        "rsdl.lm.proj": 0.4, "rsdl.lm.norm": 0.2, "rsdl.lm.layer": 0.1,
        step_scopes.NAMED: 0.15, step_scopes.UNNAMED: 0.05})
    assert sum(rows.values()) == pytest.approx(0.9)     # no ``while``


@pytest.mark.parametrize("chips", [1, 4])
def test_the_unscoped_share_is_what_no_scope_is_billed(chips, capsys):
    share = step_scopes.unscoped_pct_of_step(_facts(_OPS, _NAMES, chips),
                                             _MODULE)
    assert share == pytest.approx(20.0)
    lines = capsys.readouterr().out.splitlines()
    assert lines[0].startswith("# step scope rsdl.lm.proj: 400.0000 ms a "
                               "step, 40.0000 % of step")
    assert any(line.startswith("# step scope (none, named): 150.0000 ms")
               for line in lines)
    assert any(line.startswith("# step scope (no op_name): 50.0000 ms")
               for line in lines)
    assert lines[-1].startswith("# step scopes: billed 900.0000 ms of "
                                "1000.0000 ms a step over 1 steps")


def test_a_run_with_nothing_to_read_gives_none():
    facts = _facts(_OPS, _NAMES)
    assert step_scopes.unscoped_pct_of_step({"trace": None}, _MODULE) is None
    assert step_scopes.unscoped_pct_of_step(
        dict(facts, step_op_names={}), _MODULE) is None
    # no run of the step inside the window
    assert step_scopes.unscoped_pct_of_step(facts, "^jit_other$") is None


def test_the_parents_program_reads_a_number_too():
    """Before this PR's scopes: what had a scope is billed to it, the rest
    to none, and nothing is raised."""
    names = {"fusion.1": "jit(train_step)/jvp(jit(_project))/rsdl.lm.proj/"
                         "dot_general",
             "fusion.2": "jit(train_step)/transpose(jvp())/mul",
             "while.3": "jit(train_step)/jvp(jit(_moe_fwd))/while",
             "add.5": "jit(train_step)/jvp()/add",
             "fusion.6": "jit(train_step)/transpose(jvp(jvp()))/checkpoint/"
                         "add_any"}
    share = step_scopes.unscoped_pct_of_step(_facts(_OPS, names), _MODULE)
    assert share == pytest.approx(50.0)


@pytest.mark.parametrize("metric, cells", [
    ("step_unscoped_pct", ["dlrm_train", "bert_train", "dlrm_train_x4",
                           "mellum_train_8k", "laguna_train_8k"]),
    ("bert_mlp_pct", ["bert_train"]), ("bert_proj_pct", ["bert_train"]),
    ("dlrm_lookup_pct", ["dlrm_train", "dlrm_train_x4"]),
    ("dlrm_mlp_pct", ["dlrm_train", "dlrm_train_x4"])])
def test_the_manifest_lists_the_metric_and_its_reader_resolves(metric,
                                                               cells):
    from chipbench import manifest
    entry, = [m for m in manifest.load_manifest()["per_layer"]
              if m["name"] == metric]
    assert entry["workloads"] == cells
    assert (entry["unit"], entry["better"], entry["source"],
            entry["moves"]) == ("%", "lower", "device_trace",
                                "train_rows_per_s")
    for cell in cells:
        assert metric in [m["name"]
                          for m in manifest.resolve_cell(cell).per_layer]
    reader = manifest.layer_reader(metric)
    assert reader({"trace": None}) is None      # an untraced run
    if metric != "step_unscoped_pct":
        # a scope of the registry, read where the program has none of it
        with open(os.path.join(manifest.BENCH_DIR, "layers",
                               metric + ".json")) as f:
            assert json.load(f)["args"]["scope"] in telemetry.STEP_SCOPES
        assert reader(_facts(_OPS, _NAMES)) is None
