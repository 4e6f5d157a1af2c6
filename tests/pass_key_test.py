"""The step's PASSES (PR 70): ``analysis/cost_ledger.pass_key`` folds an
instruction's ``op_name`` into forward / replay / backward / optimizer /
unmarked, from jax's own transform wrappers, ``jax.checkpoint``'s
``rematted_computation`` and the program's scope ``replay``
(``core/scope.py replay_vjp``) — first on the strings this jax writes, then
on the compiled step of every memory strategy at toy widths: every matmul
has a pass, the replay is the forward again (less what nothing in the
backward reads), the backward twice the forward, a riding kind's layer is
not replayed, and without the mark the revnet replay is somebody else's."""
import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from backend import make_params
from homebrewnlp_tpu.analysis.cost_ledger import PASSES, pass_key, scope_key
from homebrewnlp_tpu.config import ModelParameter
from homebrewnlp_tpu.core import scope as scope_mod
from homebrewnlp_tpu.model import Model
from homebrewnlp_tpu.train import Trainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP = "jit(step_fn)/"
FWD = STEP + "jvp(gpt0)/"
BWD = STEP + "transpose(jvp(gpt0))/"
CKPT = BWD + "body0/jvp(gpt0)/body0/checkpoint/"
LINEAR = "block0_0_0/bottleneck_group_linear_0/abcd,cde->abe/dot_general"
ATTEND = "block1_0_0/attention_0/flash_attention/"

#: ``op_name``s as jax 0.9.0 writes them in the compiled steps below (and,
#: for the kernels, in a TPU trace's ``tf_op``), each with its pass
STRINGS = [
    # the forward: under the differentiated root, no transpose, no mark
    (FWD + "body0/" + LINEAR, "forward"),
    (FWD + "body0/while/body/closed_call/" + LINEAR, "forward"),
    (FWD + "input0/gather0/embed0/gather", "forward"),
    (FWD + "loss0/head_loss/bshk,hkpv->bspv/dot_general", "forward"),
    (FWD + "mul", "forward"),
    # a program that differentiates nothing: the model's scopes say forward
    ("jit(forward)/gpt0/body0/block0_1_0/attention_0/dot_general", "forward"),
    # jax.checkpoint: its replay names itself, its backward does not
    (CKPT + "rematted_computation/" + LINEAR, "replay"),
    (CKPT + "rematted_computation/block0_0_0/bottleneck_group_linear_0/"
     "relu1/jit(relu)/max", "replay"),
    (CKPT + "rematted_computation/" + ATTEND + "flash_fwd_causal/pallas_call",
     "replay"),
    (CKPT + LINEAR, "backward"),
    (CKPT + ATTEND + "flash_bwd_fused_causal/pallas_call", "backward"),
    (BWD + "body0/while/body/checkpoint/rematted_computation/closed_call/"
     + LINEAR, "replay"),
    (BWD + "body0/while/body/checkpoint/closed_call/" + LINEAR, "backward"),
    # a layer's own jax.checkpoint (model/gated_delta.py) inside a region:
    # replayed with the region, and once more for its own backward
    (CKPT + "rematted_computation/block2_0_0/gated_delta_0/delta_rule/"
     "checkpoint/dot_general", "replay"),
    (CKPT + "block2_0_0/gated_delta_0/delta_rule/checkpoint/"
     "rematted_computation/dot_general", "replay"),
    (CKPT + "block2_0_0/gated_delta_0/delta_rule/checkpoint/dot_general",
     "backward"),
    # the program's own replays: the mark round ``jax.vjp``, its inner
    # ``jvp(..)``, unrolled and scanned
    (BWD + "body0/replay/jvp(block0_0_0)/bottleneck_group_linear_0/"
     "abcd,cde->abe/dot_general", "replay"),
    (BWD + "body0/replay/jvp(block0_0_0)/bottleneck_group_linear_0/relu0/"
     "jit(relu)/max", "replay"),
    (BWD + "body0/while/body/replay/jvp(block0_1_0)/attention_0/"
     "map_mixer_fwd_causal/pallas_call", "replay"),
    # ... and the three ways jax writes the transposed half of that ``vjp``
    (BWD + "body0/transpose(jvp(block0_0_0))/bottleneck_group_linear_0/"
     "abcd,cde->abe/dot_general", "backward"),
    (BWD + "body0/while/body/transpose(replay)/jvp(block0_0_0)/norm_0/mul",
     "backward"),
    (BWD + "body0/transpose(transpose(jvp(gpt0)))/body0/replay/"
     "jvp(block0_0_0)/norm_0/mul", "backward"),
    (BWD + "body0/while/body/transpose(jvp(block0_1_0))/attention_0/"
     "map_mixer_bwd_dval_causal/pallas_call", "backward"),
    # the mark INSIDE the differentiated function (the 1F1B last stage,
    # which no outer transpose wraps), and a replay inside a backward unit
    (STEP + "shmap_body/while/body/jvp(replay)/block0_0_0/mul", "replay"),
    (STEP + "shmap_body/while/body/transpose(jvp(replay))/block0_0_0/mul",
     "backward"),
    (STEP + "shmap_body/while/body/replay/jvp(block0_0_0)/mul", "replay"),
    (STEP + "shmap_body/while/body/transpose(replay)/jvp()/replay/"
     "jvp(block0_0_0)/mul", "replay"),
    (STEP + "shmap_body/while/body/transpose(replay)/jvp()/"
     "transpose(replay)/jvp(block0_0_0)/mul", "backward"),
    # plain backward: a transpose and no mark
    (BWD + "body0/" + LINEAR, "backward"),
    (BWD + "input0/abcd,cdef->abef/dot_general", "backward"),
    (BWD + "loss0/head_loss/mul", "backward"),
    # the optimizer, wherever its scope stands
    (STEP + "optimizer/mul", "optimizer"),
    (STEP + "while/body/optimizer/gpt0/body0/block0_0_0/add", "optimizer"),
    # no model scope, no transform: the step's glue, XLA's own
    (STEP + "reduce_sum", "unmarked"),
    (STEP + "integer_pow", "unmarked"),
    ("state.variables['gpt0/body0/block0_0_0/norm_0/normal_var0/var0']",
     "unmarked"),
    ("", "unmarked"),
]


@pytest.mark.parametrize("path,want", STRINGS,
                         ids=[f"{i}-{w}" for i, (_, w) in enumerate(STRINGS)])
def pass_key_reads_the_pass_jax_wrote_test(path, want):
    assert pass_key(path) == want
    assert want in PASSES


#: one path a scope family the ledger reads (``BENCHMARK.json``'s
#: ``scope_*`` metrics and the scoped rooflines), below the body's region
FAMILIES = {
    "body/attention": "block0_1_0/attention_0/flash_fwd_causal/pallas_call",
    "body/attention/q_down": "block0_1_0/attention_0/q_down/dot_general",
    "body/attention/sparse_attention/index":
        "block0_1_0/attention_0/sparse_attention/index/dot_general",
    "body/attention/sparse_attention/index_loss":
        "block0_1_0/attention_0/sparse_attention/index_loss/mul",
    "body/bottleneck_group_linear": LINEAR,
    "body/norm": "block0_0_0/norm_0/rsqrt",
    "body/mlp": "block0_1_0/mlp_0/dot_general",
    "body/merge": "block0_1_0/merge/add",
    "body/moe/experts": "block0_1_0/moe_0/experts/gmm/pallas_call",
    "body/moe/router/mlp": "block0_1_0/moe_0/router/mlp/dot_general",
    "body/moe/latent_down": "block0_1_0/moe_0/latent_down/dot_general",
    "body/mamba/ssd": "block0_0_0/mamba_0/ssd/ssd_scan_fwd/pallas_call",
    "body/gated_delta/delta_rule":
        "block0_0_0/gated_delta_0/delta_rule/delta_rule_fwd/pallas_call",
    "body/kda/rule": "block0_0_0/kda_0/rule/kda_rule_fwd/pallas_call",
    "body/lightning/rule": "block0_0_0/lightning_0/rule/intra_chunk/dot",
    "body/cca/conv": "block0_0_0/cca_0/conv/conv_general_dilated",
    "denoise/split": "block0_0_0/denoise/split/slice",
}
#: where jax puts the marks round such a path
MARKED = ["replay/jvp({})", "while/body/replay/jvp({})",
          "while/body/transpose(replay)/jvp({})",
          "jvp(gpt0)/body0/checkpoint/rematted_computation/{}",
          "jvp(gpt0)/body0/checkpoint/{}"]


@pytest.mark.parametrize("scope", sorted(FAMILIES))
def the_marks_move_no_scope_test(scope):
    """``scope_key`` folds a path with a mark to the scope it folds the path
    to without it: every ``scope_*_time_share`` stays where it is."""
    tail = FAMILIES[scope]
    block, rest = tail.split("/", 1)
    assert scope_key(BWD + "body0/" + tail) == scope
    for form in MARKED:
        marked = BWD + "body0/" + form.format(block) + "/" + rest
        assert scope_key(marked) == scope, marked
        assert scope_key("jit(step_fn)/transpose(jvp(gpt0))/mtp0/" + "body0/"
                         + form.format(block) + "/" + rest) == "mtp/" + scope


@pytest.mark.parametrize("path,scope", [
    (BWD + "loss0/replay/head_loss/mul", "head_loss"),
    (STEP + "replay/optimizer/mul", "optimizer"),
    (BWD + "input0/replay/gather0/embed0/gather", "input/embed"),
    (BWD + "output0/checkpoint/rematted_computation/embed0/dot_general",
     "output/unembed"),
    (BWD + "loop0/replay/exit_gate/dot_general", "exit_gate"),
    (STEP + "replay/reduce_sum", "unscoped"),
])
def the_marks_move_no_scope_outside_the_body_test(path, scope):
    assert scope_key(path) == scope
    assert scope_key(path.replace("replay/", "").replace(
        "checkpoint/rematted_computation/", "")) == scope


def the_mark_is_the_scope_modules_constant_test():
    """``cost_ledger`` mirrors the constant (it imports no jax)."""
    from homebrewnlp_tpu.analysis import cost_ledger
    assert scope_mod.REPLAY in cost_ledger._REPLAY_MARKS
    assert cost_ledger._REPLAY_MARKS == {scope_mod.REPLAY,
                                         "rematted_computation"}


# ---- the compiled step of every strategy, toy widths, on the CPU ------------

_CFG = dict(sequence_length=32, features_per_head=16, heads=2, depth=2,
            train_batch_size=4, vocab_size=64, remat_policy="recompute",
            optimizer="momentum:0.9:1:1-learning_rate", learning_rate=0.01)
STRATEGIES = {"checkpoint": ("checkpoint", False),
              "checkpoint_scan": ("checkpoint", True),
              "revnet": ("revnet", False), "momentum": ("momentum", False),
              "rev_scan": ("revnet", True), "mom_scan": ("momentum", True)}
_DEF = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?(\w+)\[([\d,]*)\]")
_DOT = re.compile(r" dot\((?:\w+\[([\d,]*)\]\S* )?%?([\w.\-]+), "
                  r".*?lhs_contracting_dims=\{([\d,]*)\}")
_BLOCK = re.compile(r"block\d+_\d+_\d+")


def _dims(text):
    return [int(d) for d in text.split(",") if d]


def matmuls(hlo: str):
    """``[(op_name, flops)]`` of every ``dot`` of a module's text, compiled
    or lowered (2 x the result's elements x the contracted sizes; a loop's
    body counts once, as the forward's and the backward's loops run
    alike)."""
    shapes, out = {}, []
    for line in hlo.splitlines():
        found = _DEF.match(line)
        if found:
            shapes[found.group(1)] = _dims(found.group(3))
    for line in hlo.splitlines():
        if " dot(" not in line:
            continue
        dot, result = _DOT.search(line), _DEF.match(line)
        lhs = shapes[dot.group(2)] if dot.group(1) is None \
            else _dims(dot.group(1))
        name = re.search(r'op_name="([^"]*)"', line)
        out.append((name.group(1) if name else "", 2 * int(
            np.prod(_dims(result.group(3)), dtype=np.int64)
            * np.prod([lhs[c] for c in _dims(dot.group(3))], dtype=np.int64))))
    return out


def _lowered(trainer, params):
    x = np.random.default_rng(0).integers(
        0, params.vocab_size,
        (params.train_batch_size, params.sequence_length, 1))
    batch = {"token_x": jnp.asarray(x),
             "token_y": jnp.asarray((x + 1) % params.vocab_size)}
    return trainer.lowered(trainer.init_state(batch), batch)


def _compiled(trainer, params):
    return _lowered(trainer, params).compile().as_text()


def _as_traced(lowered) -> str:
    """The lowered module as HLO text with ``op_name``s and operand shapes:
    the program as jax TRACED it, before XLA merges what computes the same
    (it folds an unrolled strategy's last replay into the forward it
    repeats)."""
    from jax._src.lib import xla_client
    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = options.print_operand_shape = True
    return lowered.compiler_ir(dialect="hlo").get_hlo_module().to_string(
        options)


@pytest.fixture(scope="module")
def steps():
    """``name -> (compiled, traced)``, each ``[(op_name, flops)]``, of the
    toy step, made once a module for every assertion that reads it.
    ``traced`` is None for a scanned strategy: a scanned body's ``op_name``s
    stand whole only once XLA has inlined it."""
    made = {}

    def get(name):
        if name not in made:
            strategy, scan = STRATEGIES[name]
            params = make_params(memory_reduction_strategy=strategy,
                                 scan_layers=scan, **_CFG)
            lowered = _lowered(Trainer(params, Model(params)), params)
            made[name] = (matmuls(lowered.compile().as_text()),
                          None if scan else matmuls(_as_traced(lowered)))
        return made[name]
    return get


def _counted(steps, name):
    """The matmuls the FLOP comparisons read: as traced where the strategy
    is unrolled, compiled where it is scanned."""
    compiled, traced = steps(name)
    return compiled if traced is None else traced


def _by_pass_and_block(dots):
    """``{pass: {block: flops}}`` of the matmuls inside the body's blocks."""
    out = collections.defaultdict(collections.Counter)
    for name, flops in dots:
        block = _BLOCK.search(name)
        if block and scope_key(name).startswith("body/"):
            out[pass_key(name)][block.group(0)] += flops
    return out


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def every_matmul_of_the_step_has_a_pass_test(steps, name):
    dots = steps(name)[0]
    assert len(dots) > 20
    passes = collections.Counter(pass_key(op) for op, _ in dots)
    assert passes["unmarked"] == 0, [op for op, _ in dots
                                     if pass_key(op) == "unmarked"]
    assert passes["optimizer"] == 0
    assert min(passes[p] for p in ("forward", "replay", "backward")) > 0


@pytest.mark.parametrize("name", sorted(STRATEGIES))
def the_backward_is_twice_the_forward_test(steps, name):
    """Nothing rides (``remat_policy: "recompute"``): every forward matmul
    of a block has its two backward matmuls."""
    by = _by_pass_and_block(_counted(steps, name))
    assert set(by["backward"]) == set(by["forward"]) and by["forward"]
    for block, flops in by["forward"].items():
        assert by["backward"][block] == 2 * flops, block


@pytest.mark.parametrize("name", ["rev_scan", "mom_scan"])
def a_scanned_replay_is_the_forward_again_test(steps, name):
    by = _by_pass_and_block(_counted(steps, name))
    assert by["replay"] == by["forward"]


@pytest.mark.parametrize("name", ["revnet", "momentum"])
def an_unrolled_replay_is_the_forward_less_the_first_blocks_value_test(
        steps, name):
    """The replayed VALUE of a block rebuilds the stream that entered it;
    nothing reads the stream that entered the first block, so jax drops the
    matmul that makes it (the block's last).  Every other block is replayed
    whole."""
    dots = _counted(steps, name)
    by = _by_pass_and_block(dots)
    first = "block0_0_0"
    for block, flops in by["forward"].items():
        if block != first:
            assert by["replay"][block] == flops, block
    short = by["forward"][first] - by["replay"][first]
    assert short in {flops for op, flops in dots if first in op
                     and pass_key(op) == "forward"}


@pytest.mark.parametrize("name", ["checkpoint", "checkpoint_scan"])
def a_checkpoint_replay_is_the_forward_less_each_blocks_last_matmul_test(
        steps, name):
    """``jax.checkpoint`` replays what the backward reads: a block's last
    matmul makes only the block's output, which nothing inside reads
    (PR 52: "a pre-norm block's replay never runs the down matmul")."""
    dots = _counted(steps, name)
    by = _by_pass_and_block(dots)
    assert set(by["replay"]) == set(by["forward"])
    for block, flops in by["forward"].items():
        short = flops - by["replay"][block]
        assert short in {f for op, f in dots if block in op
                         and pass_key(op) == "forward"}, (block, short)


def without_the_mark_the_revnet_replay_is_somebody_elses_test(monkeypatch):
    """The negative control: ``replay_vjp`` without its scope compiles the
    same matmuls and ``pass_key`` finds no replay among them — the revnet
    tests above can fail."""
    monkeypatch.setattr(scope_mod, "replay_vjp",
                        lambda fn, *primals: jax.vjp(fn, *primals))
    params = make_params(memory_reduction_strategy="revnet",
                         scan_layers=True, **_CFG)
    by = _by_pass_and_block(matmuls(_compiled(Trainer(params, Model(params)),
                                              params)))
    assert not by["replay"]
    # the transposed half still reads backward; the replayed half, a
    # ``jvp(..)`` under the outer transpose, reads backward too
    assert sum(by["backward"].values()) == 3 * sum(by["forward"].values())


# ---- a kind rides: its layer is not replayed --------------------------------

def _block(*layers):
    return {"skip": True, "layer": list(layers)}


def _attention_step(policy):
    """Two pre-norm blocks a layer (grouped-query attention, an MLP) under
    ``checkpoint``, one whole tile of the flash route."""
    with open(os.path.join(REPO, "configs", "olmoe_1b_7b.json")) as f:
        config = {**json.load(f), "depth": 2, "heads": 4,
                  "features_per_head": 16, "sequence_length": 128,
                  "train_batch_size": 2, "vocab_size": 272, "tpu_size": 1,
                  "use_checkpointing": False, "calculation_dtype": "float32",
                  "memory_reduction_strategy": "checkpoint",
                  "remat_policy": policy, "block_config": [
                      _block("norm-rms-scale",
                             "attention-rope-q_heads4-kv_heads2"),
                      _block("norm-rms-scale", "mlp-silu")]}
    params = ModelParameter(config)
    assert not params.unknown_config_keys
    return matmuls(_compiled(Trainer(params, Model(params)), params))


def _attends(dots, which):
    """Matmuls of pass ``which`` inside the attention computation itself
    (the dense form's scope or the flash route's), not the projections round
    it."""
    return [op for op, _ in dots if pass_key(op) == which
            and re.search("/(attention_dense|flash_attention)/", op)]


def where_attention_rides_checkpoint_the_replay_attends_nothing_test():
    """Kind ``attention`` (``remat_policy: "stash"`` names it at toy sizes):
    the block's policy saves the layer's ``(out, lse)``, so the replay holds
    the projections and no matmul of the attention; with nothing riding it
    holds the forward's."""
    plain, riding = _attention_step("recompute"), _attention_step("stash")
    assert _attends(plain, "forward") and _attends(riding, "forward")
    assert len(_attends(plain, "replay")) == len(_attends(plain, "forward"))
    assert not _attends(riding, "replay")
    assert _attends(riding, "backward")
    projections = [op for op, _ in riding if pass_key(op) == "replay"
                   and scope_key(op) == "body/attention"]
    assert projections


def _bottleneck_step(policy):
    from homebrewnlp_tpu.core import sharding as shardlib
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 virtual devices")
    params = make_params(**{
        **_CFG, "heads": 4, "train_batch_size": 8,
        "calculation_dtype": "float32", "memory_reduction_strategy": "revnet",
        "scan_layers": True, "remat_policy": policy, "tpu_size": 4,
        "mesh_shape_override": {"data": 2, "model": 2}})
    mesh = shardlib.build_mesh(params, jax.devices()[:4])
    return matmuls(_compiled(Trainer(params, Model(params), mesh=mesh),
                             params))


def where_the_bottleneck_rides_revnet_its_in_projection_is_not_replayed_test():
    """Kind ``bottleneck`` (a ``model`` axis: the in-projection's all-reduced
    output rides the residuals): the replay starts behind it."""
    def einsums(dots, which):
        return collections.Counter(
            re.search(r"bottleneck_group_linear_\d+/([^/]+)/", op).group(1)
            for op, _ in dots if pass_key(op) == which
            and scope_key(op) == "body/bottleneck_group_linear")
    plain, riding = _bottleneck_step("recompute"), _bottleneck_step("auto")
    assert einsums(plain, "replay") == einsums(plain, "forward")
    assert einsums(riding, "forward") == einsums(plain, "forward")
    gone = einsums(riding, "forward") - einsums(riding, "replay")
    # the contraction over the sharded heads: [b, s, h, f] x [h, f, i]
    assert gone == {"abcd,cde->abe": 1}, gone


def benchmark_lists_the_four_metrics_test():
    """``BENCHMARK.json``: PR 70's four entries in its order, each on every
    train cell, each with its file agreeing on layer and end-to-end
    metric."""
    from benchmark.lib import cell as cell_mod
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = [w["name"] for w in bench["workloads"]]
    names = ["pass_forward_time_share", "pass_replay_time_share",
             "pass_backward_time_share", "remat_stash_share"]
    mine = [m for m in bench["per_layer"] if m["name"] in names]
    assert [m["name"] for m in mine] == names
    for entry in mine:
        mod = cell_mod.load_metric(entry["name"])
        assert entry["workloads"] == cells
        assert (entry["layer"], entry["moves"], entry["unit"],
                entry["better"]) == (mod.LAYER, mod.MOVES, "%", "lower")
        assert entry["layer"] == "L3_model_graph" and mod.__doc__
        assert entry["source"] == ("program_counter" if entry["name"]
                                   == "remat_stash_share" else "device_trace")
