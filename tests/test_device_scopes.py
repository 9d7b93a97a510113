"""Device time by named scope (``profiler.device_time_by_scope``): the
reducer on a recorded neutral trace, the scopes of the LM step kept whole,
the ``DEVICE_SCOPES`` rule, and ``dumps()`` printing the device's table."""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import profiler

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "device_scope")
ROOT = os.path.dirname(HERE)


@pytest.fixture(scope="module")
def reduced():
    with open(os.path.join(FIXTURE, "step.hlo.txt")) as f:
        hlo = f.read()
    return profiler.device_time_by_scope(FIXTURE, hlo=hlo, steps=2)


def _row(reduced, leaf, pas):
    rows = [r for r in reduced["rows"] if r[0] == leaf and r[1] == pas]
    assert len(rows) == 1, (leaf, pas, reduced["rows"])
    return rows[0]


# a step of the fixture (ns; ``fixtures/device_scope/make_trace.py``): a
# weight's copy the compiler made 20, the qkv product 400, a qkv/rope fusion
# 300, flash forward 500, a while of 1000 spanning a product of 400 (with
# the norm's larger backward fused round it) and flash dQ of 500, a gather
# of the second forward 200 (its fused path cut short, as XLA leaves them),
# the optimizer 150, an instance the text does not know 50; between the
# steps another program's op of 100
@pytest.mark.parametrize("leaf, pas, ns, calls, category", [
    ("attn.qkv", "fwd", 20 + 400 + 300, None, None),
    ("flash_fwd", "fwd", 500, 1.0, "custom-call"),
    ("flash_dq", "bwd", 500, 1.0, "custom-call"),
    ("mlp.up", "bwd", 400, 1.0, "convolution fusion"),
    ("moe.dispatch.gather", "remat", 200, 1.0, "loop fusion"),
    ("optimizer", "-", 150, 1.0, "loop fusion"),
])
def test_sums_by_leaf_and_pass(reduced, leaf, pas, ns, calls, category):
    rows = [r for r in reduced["rows"] if r[0] == leaf and r[1] == pas]
    assert sum(r[2] for r in rows) == pytest.approx(ns * 1e-6)
    if calls is not None:
        assert _row(reduced, leaf, pas)[4:] == (calls, category)


def test_a_while_keeps_only_what_its_body_does_not_cover(reduced):
    """The loop spans a product and a kernel: their time is theirs, and the
    100 ns that are the loop's own lie under no scope."""
    own = [r for r in reduced["rows"] if r[5] == "while"]
    assert [(r[0], r[1]) for r in own] == [(profiler.UNSCOPED, "-")]
    assert own[0][2] == pytest.approx(100e-6)


def test_a_fusion_across_two_scopes_is_listed_as_mixed(reduced):
    """60 % of fusion.2's bytes lie under attn.qkv, 40 % under attn.rope:
    its time goes to the larger and is counted as mixed too."""
    assert reduced["mixed_ms"] == pytest.approx((300 + 400) * 1e-6)
    mixed = {m[0]: m for m in reduced["mixed"]}
    _, ms, split = mixed["fusion.2"]
    assert ms == pytest.approx(300e-6)
    assert [k for k, _ in split] == [("attn.qkv", "fwd"),
                                     ("attn.rope", "fwd")]
    assert [s for _, s in split] == pytest.approx([0.6, 0.4])


def test_a_product_has_its_fusion_whatever_rides_along(reduced):
    """fusion.10 holds the ``w_up`` gradient's product and five times its
    bytes of the norm's backward: the MXU's time is the product's, so the
    row is ``mlp.up`` (``convolution fusion``) and the fusion is listed as
    mixed, the product's leaf first."""
    assert _row(reduced, "mlp.up", "bwd")[5] == "convolution fusion"
    assert not [r for r in reduced["rows"] if r[0] == "norm"]
    _, ms, split = {m[0]: m for m in reduced["mixed"]}["fusion.10"]
    assert ms == pytest.approx(400e-6)
    assert [k for k, _ in split] == [("mlp.up", "bwd"), ("norm", "bwd")]
    assert [s for _, s in split] == pytest.approx([1 / 6, 5 / 6])


def test_a_fused_path_cut_short_takes_the_fusions_pass(reduced):
    """Inside fusion.3 the gather's path is ``mlp/moe.dispatch/...`` with
    no ``jit(`` and no transform: the fusion's own path says ``remat``."""
    assert _row(reduced, "moe.dispatch.gather", "remat")[2] == \
        pytest.approx(200e-6)


def test_what_the_compiler_made_is_placed_by_its_user(reduced):
    """copy.7 has no metadata: it feeds the qkv product, so it is
    ``attn.qkv``'s, and ``inherited_ms`` says how much was placed so."""
    row, = [r for r in reduced["rows"] if r[5] == "copy"]
    assert row[:2] == ("attn.qkv", "fwd") and row[2] == pytest.approx(20e-6)
    assert reduced["inherited_ms"] == pytest.approx(20e-6)


def test_what_cannot_be_placed_is_unscoped_never_dropped(reduced):
    """An instance the text does not hold, the loop's own time, and an
    instance of another program that shares a name with one of the step's."""
    assert dict(reduced["unscoped"]) == pytest.approx(
        {"fusion.999": 50e-6, "while.1": 100e-6, "fusion.1": 50e-6})
    assert reduced["unscoped_ms"] == pytest.approx(200e-6)


def test_rows_sum_to_busy_time(reduced):
    assert sum(r[2] for r in reduced["rows"]) == pytest.approx(
        reduced["busy_ms"])
    assert reduced["busy_ms"] == pytest.approx((2 * 2620 + 100) / 2 * 1e-6)
    assert reduced["window_ms"] == pytest.approx(7040 / 2 * 1e-6)
    assert sum(r[3] for r in reduced["rows"]) == pytest.approx(1.0)


def test_gaps_go_to_the_shortest_engine_span_over_them(reduced):
    """200 ns a step idle under both engine.sync and engine.fused.dispatch:
    the shorter has them; between the steps 300 + 1000 ns under engine.sync
    alone, the 1000 being the longest single gap."""
    assert dict(reduced["gaps"]) == pytest.approx(
        {"engine.fused.dispatch": 200e-6, "engine.sync": 650e-6})
    assert reduced["longest_gap"] == ("engine.sync", pytest.approx(1000e-6))


def test_the_table_names_every_part(reduced):
    table = profiler.device_table(reduced)
    for part in ("unscoped", "mixed fusion.2", "attn.rope fwd 40 %",
                 "idle  engine.sync", "longest single gap", "flash_dq"):
        assert part in table, table
    short = profiler.device_table(reduced, min_share=0.1)
    assert "(rows under 10.0 %)" in short and "optimizer" not in short


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/jvp(attn)/attn.qkv/attn.rope/mul", ("attn.rope", "fwd")),
    ("jit(step)/transpose(jvp(attn))/attn.qkv/transpose",
     ("attn.qkv", "bwd")),
    # the primitive ``transpose`` is no pass
    ("jit(step)/jvp(attn)/attn.qkv/transpose", ("attn.qkv", "fwd")),
    ("jit(step)/transpose(jvp())/while/body/checkpoint/"
     "rematted_computation/mlp/moe.dispatch/moe.dispatch.sort/sort",
     ("moe.dispatch.sort", "remat")),
    ("jit(step)/optimizer/sub", ("optimizer", "-")),
    # a jitted function's name is no scope
    ("jit(step)/jvp()/jit(norm)/mul", (None, "fwd")),
    ("jit(step)/jvp(mlp)/moe.experts/jit(clip)/max", ("moe.experts", "fwd")),
    ("jit(step)/allreduce.dp_fsdp/psum", ("allreduce", "-")),
    ("", (None, "-")),
])
def test_leaf_and_pass_of_a_path(op_name, want):
    assert profiler._leaf_and_pass(op_name) == want


# -- the LM step's scopes stay whole ---------------------------------------
# opcodes that cost device time and must lie under a leaf
_HELD = ("fusion", "dot", "convolution", "custom-call", "sort", "gather",
         "scatter")
# (opcode, why) excused beyond parameters, tuples, bitcasts, copies and
# constants, which the rule never looks at
_EXCUSED = {}


def _lm_step_text(config_name, batch=2, seq=64):
    from mxnet_tpu.models import transformer as tr

    with open(os.path.join(ROOT, "benchmarks", "configs",
                           config_name + ".json")) as f:
        cfg = json.load(f)
    m = dict(cfg["model"])
    m.update(cfg["rehearsal"]["model"])
    model = tr.TransformerLM(tr.TransformerConfig(**m))
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    lowered = jax.jit(tr.make_train_step(model, lr=0.01, momentum=0.9)).lower(
        shapes, shapes, tok, tok)
    # the persistent cache's key leaves metadata out: a program found there
    # would carry the scopes of whatever tree compiled it
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return lowered.compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


@pytest.mark.parametrize("config_name", [
    "pythia-1.4b-sizes", "jamba2-3b-l14", "deepseek-v2-lite-l5-e32",
    "smallthinker-21b-l4-e16", "lfm2-8b-a1b-l5-e8", "laguna-s2.1-l5-e16"])
def test_every_op_of_the_lm_step_lies_under_a_leaf(config_name):
    """Compile the family's train step at its rehearsal size: every fusion,
    product, custom call, sort, gather and scatter of the module resolves to
    a leaf in DEVICE_SCOPES.  New code in the step needs a scope (or a line
    in ``_EXCUSED`` with its reason), or the table of a profile would grow
    an unscoped row."""
    module = profiler._Module(_lm_step_text(config_name))
    held = [n for n, (opcode, _name, _c, _k, _o) in module.inst.items()
            if opcode in _HELD and opcode not in _EXCUSED]
    assert len(held) > 20, "the text was not parsed: %d" % len(held)
    lost = [(n, module.inst[n][0], module.inst[n][1]) for n in held
            if module.place(n)[0] == profiler.UNSCOPED]
    assert not lost, lost[:10]
    leaves = {module.place(n)[0] for n in held}
    assert leaves <= set(profiler.DEVICE_SCOPES)
    # the leaves the issue asked for are really there
    want = {"embed", "head", "optimizer", "mlp.up" if config_name in (
        "pythia-1.4b-sizes", "jamba2-3b-l14", "deepseek-v2-lite-l5-e32",
        "lfm2-8b-a1b-l5-e8") else "moe.combine.sum"}
    if config_name == "lfm2-8b-a1b-l5-e8":
        # the convolution mixer (its lax form here, under the container),
        # the per-head norms, the bias's own update
        want |= {"sconv", "sconv.in_proj", "sconv.out_proj", "attn.qk_norm",
                 "moe.bias_update"}
    if config_name == "laguna-s2.1-l5-e16":
        # the per-head output gate and the partial rotary term
        want |= {"attn.gate", "attn.rope"}
    assert want <= leaves, sorted(leaves)
    passes = {module.place(n)[1] for n in held}
    assert {"fwd", "bwd", "remat", "-"} <= passes, passes


def test_a_comparators_fusion_has_its_sorts_place():
    """XLA's CPU compiler fuses a sort's comparator and leaves the fusion
    no metadata; it has no user either.  It takes the place of the sort
    that applies its computation."""
    module = profiler._Module("""HloModule jit_step

%fused_computation.1 (param_0: f32[], param_1: f32[]) -> pred[] {
  %param_0 = f32[] parameter(0)
  %param_1 = f32[] parameter(1)
  ROOT %compare.1 = pred[] compare(%param_0, %param_1), direction=GT
}

%compare-greater-than.1 (p.0.lhs: f32[], p.0.rhs: f32[]) -> pred[] {
  %p.0.lhs = f32[] parameter(0)
  %p.0.rhs = f32[] parameter(1)
  ROOT %select_compare_fusion = pred[] fusion(%p.0.rhs, %p.0.lhs), kind=kLoop, calls=%fused_computation.1
}

ENTRY %main (x: f32[64]) -> f32[64] {
  %x = f32[64]{0} parameter(0)
  ROOT %sort.1 = f32[64]{0} sort(%x), dimensions={0}, to_apply=%compare-greater-than.1, metadata={op_name="jit(step)/transpose(jvp(layers))/checkpoint/rematted_computation/mlp/moe.route/sort"}
}
""")
    assert module.place("select_compare_fusion") == (
        "moe.route", "remat", "loop fusion", None, True)


def test_the_wrapper_round_value_and_grad_is_no_scope():
    """``loss`` used to wrap the whole ``value_and_grad``, so every op of
    the step lay under it; now it holds the loss's own arithmetic."""
    module = profiler._Module(_lm_step_text("pythia-1.4b-sizes"))
    under = [n for n in module.inst if module.place(n)[0] == "loss"]
    products = [n for n in module.inst
                if module.place(n)[2] == "convolution fusion"
                or module.inst[n][0] in ("dot", "convolution")]
    assert products and not set(under) & set(products)


# -- the rule ---------------------------------------------------------------
def _package_sources():
    root = os.path.dirname(os.path.abspath(mx.__file__))
    for dirpath, _dirs, files in os.walk(root):
        for f in files:
            if f.endswith(".py"):
                path = os.path.join(dirpath, f)
                with open(path) as fh:
                    yield path, fh.read()


def test_every_named_scope_the_package_opens_is_in_DEVICE_SCOPES():
    """Grep the package: a literal given to ``jax.named_scope`` must be in
    ``profiler.DEVICE_SCOPES`` (a collective's, built as ``<what>.<axes>``,
    by its ``<what>``), or the device's table would file its operations
    under the scope round it; and every name in the tuple is a literal
    somewhere in the package."""
    call = re.compile(r"named_scope\(\s*[\"']([^\"']+)[\"']")
    collective = re.compile(r"\b_scope\(\s*[\"'](\w+)[\"']")
    opened, literals = set(), ""
    for path, src in _package_sources():
        if path.endswith("profiler.py"):
            continue
        literals += src
        opened |= {n.split("%", 1)[0] for n in call.findall(src)}
        if path.endswith(os.path.join("parallel", "collectives.py")):
            opened |= set(collective.findall(src))
    opened.discard("")                      # the "%s.%s" of a collective
    assert len(opened) > 30, "the grep found too few scopes: %s" % opened
    assert opened <= set(profiler.DEVICE_SCOPES), \
        sorted(opened - set(profiler.DEVICE_SCOPES))
    nowhere = [n for n in profiler.DEVICE_SCOPES
               if '"%s"' % n not in literals and "'%s'" % n not in literals]
    assert not nowhere, "in DEVICE_SCOPES, opened nowhere: %s" % nowhere
    assert len(set(profiler.DEVICE_SCOPES)) == len(profiler.DEVICE_SCOPES)


# -- dumps() ------------------------------------------------------------------
@pytest.fixture
def session():
    yield
    profiler.set_config(aggregate_stats=False, tensorboard_dir=None)
    profiler.dumps(reset=True)


def _host_rows():
    profiler.set_state("run")
    with profiler.Task(profiler.ProfileDomain("test"), "a_task"):
        pass
    profiler.set_state("stop")


def test_dumps_prints_the_device_table_after_the_host_table(
        session, monkeypatch):
    """With ``aggregate_stats`` and a trace directory that holds a trace:
    both tables, the host's first."""
    monkeypatch.setattr(profiler, "_maybe_start_device_trace", lambda: None)
    profiler.set_config(aggregate_stats=True, tensorboard_dir=FIXTURE)
    _host_rows()
    out = profiler.dumps()
    assert "test::a_task" in out
    assert out.index("Total(ms)") < out.index("device time") \
        < out.index("HLO category")
    assert "custom-call" in out and "idle  engine.sync" in out


def test_dumps_prints_the_host_table_alone_without_a_trace(
        session, tmp_path, monkeypatch):
    monkeypatch.setattr(profiler, "_maybe_start_device_trace", lambda: None)
    profiler.set_config(aggregate_stats=True, tensorboard_dir=str(tmp_path))
    _host_rows()
    out = profiler.dumps()
    assert "test::a_task" in out and "device time" not in out
    profiler.set_config(tensorboard_dir=None)
    assert "device time" not in profiler.dumps()
