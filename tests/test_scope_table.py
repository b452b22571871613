"""The compiled train step's instruction -> scope table
(``obs/scopes.py``), which ``record: compile`` carries so that a device
trace -- whose operations carry an instruction's name and no scope --
can be summed by the program's own ``jax.named_scope`` parts.

First on a hand-written HLO text (a fusion whose members span two
scopes, one whose only foreign members are bitcasts and constants, a
Mosaic call, a ``while`` body and condition, a ``conditional`` branch,
what the compiler made itself, program code outside every scope); then
on the real step, compiled at toy size on the CPU by ``Trainer.train()``
for the one-device FM and field-aware paths and the hand-sharded step on
a 2x2 mesh of four CPU devices: every part of the step is named, and
the run's ``record: compile`` carries the table.  Two programs that
differ only in their scopes, through one persistent compile cache, each
get their own table; a table that cannot be made says so in the
record."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from fast_tffm_tpu import obs, platform
from fast_tffm_tpu.config import FmConfig
from fast_tffm_tpu.obs import scopes as scopes_lib
from fast_tffm_tpu.ops import sparse_apply
from fast_tffm_tpu.parallel import mesh as mesh_lib
from fast_tffm_tpu.train.loop import Trainer

HLO = """\
HloModule jit_scan_health_step, is_scheduled=true

FileNames
1 "fast_tffm_tpu/train/sparse.py"

%fused_a (param_0: f32[8], param_1: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %param_1 = f32[8]{0} parameter(1)
  %mul.1 = f32[8]{0} multiply(%param_0, %param_1), metadata={op_name="jit(scan_health_step)/while/body/tffm.row_gather/mul" stack_frame_id=3}
  ROOT %add.1 = f32[8]{0} add(%mul.1, %param_1), metadata={op_name="jit(scan_health_step)/jvp(tffm.loss)/tffm.fm_interaction_fwd/add"}
}

%fused_b (param_0.1: f32[8]) -> f32[8] {
  %param_0.1 = f32[8]{0} parameter(0)
  %bitcast.1 = f32[8]{0} bitcast(%param_0.1), metadata={op_name="jit(scan_health_step)/tffm.health/reshape"}
  %constant.1 = f32[] constant(2), metadata={op_name="jit(scan_health_step)/tffm.health/mul"}
  %broadcast.1 = f32[8]{0} broadcast(%constant.1), dimensions={}
  ROOT %multiply.2 = f32[8]{0} multiply(%bitcast.1, %broadcast.1), metadata={op_name="jit(scan_health_step)/transpose(jvp(tffm.loss))/mul"}
}

%fused_d (param_0.2: f32[8]) -> f32[8] {
  %param_0.2 = f32[8]{0} parameter(0)
  ROOT %gather.1 = f32[8]{0} negate(%param_0.2), metadata={op_name="jit(scan_health_step)/tffm.row_gather/gather"}
}

%fused_e (param_0.3: f32[8]) -> f32[8] {
  %param_0.3 = f32[8]{0} parameter(0)
  ROOT %scatter.1 = f32[8]{0} negate(%param_0.3), metadata={op_name="jit(scan_health_step)/tffm.apply_write/scatter"}
}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p = (s32[], f32[8]{0}) parameter(0)
  %gte.0 = s32[] get-tuple-element(%p), index=0
  %gte.1 = f32[8]{0} get-tuple-element(%p), index=1
  %one = s32[] constant(1)
  %add.2 = s32[] add(%gte.0, %one), metadata={op_name="jit(scan_health_step)/tffm.apply_write/while/body/add"}
  %fusion.4 = f32[8]{0} fusion(%gte.1), kind=kLoop, calls=%fused_e, metadata={op_name="jit(scan_health_step)/tffm.apply_write/scatter"}
  ROOT %tuple.1 = (s32[], f32[8]{0}) tuple(%add.2, %fusion.4)
}

%cond (p.1: (s32[], f32[8])) -> pred[] {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %gte.2 = s32[] get-tuple-element(%p.1), index=0
  %c.5 = s32[] constant(5)
  ROOT %lt.1 = pred[] compare(%gte.2, %c.5), direction=LT, metadata={op_name="jit(scan_health_step)/tffm.apply_write/while/cond/lt"}
}

%branch_true (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %neg.1 = f32[8]{0} negate(%x), metadata={op_name="jit(scan_health_step)/tffm.exchange/tffm.exchange_merge/neg"}
}

%branch_false (y: f32[8]) -> f32[8] {
  %y = f32[8]{0} parameter(0)
  ROOT %copy.9 = f32[8]{0:T(256)} copy(%y)
}

ENTRY %main.99 (Arg_0.1: f32[8], Arg_1.2: pred[]) -> (f32[8], f32[8]) {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.params.table"}
  %Arg_1.2 = pred[] parameter(1)
  %copy.1 = f32[8]{0:T(256)} copy(%Arg_0.1), metadata={op_name="state.params.table"}
  %fusion.3 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_d, metadata={op_name="jit(scan_health_step)/tffm.row_gather/gather"}
  %fusion.1 = f32[8]{0} fusion(%fusion.3, %Arg_0.1), kind=kLoop, calls=%fused_a, metadata={op_name="jit(scan_health_step)/tffm.fm_interaction_fwd/add"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_b, metadata={op_name="jit(scan_health_step)/transpose(jvp(tffm.loss))/mul"}
  %tffm.apply_write.2 = f32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(scan_health_step)/tffm.apply_write/pallas_call"}
  %c.0 = s32[] constant(0)
  %tuple.0 = (s32[], f32[8]{0}) tuple(%c.0, %tffm.apply_write.2)
  %while.1 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond, body=%body, metadata={op_name="jit(scan_health_step)/tffm.apply_write/while"}
  %gte.9 = f32[8]{0} get-tuple-element(%while.1), index=1
  %conditional.1 = f32[8]{0} conditional(%Arg_1.2, %gte.9, %gte.9), branch_computations={%branch_true, %branch_false}, metadata={op_name="jit(scan_health_step)/tffm.exchange/cond"}
  %add.9 = f32[8]{0} add(%conditional.1, %conditional.1), metadata={op_name="jit(scan_health_step)/while/body/add"}
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%fusion.2)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  ROOT %tuple.9 = (f32[8]{0}, f32[8]{0}) tuple(%add.9, %copy-done.1)
}
"""


def test_the_table_of_a_written_module():
    tab = scopes_lib.scope_table(HLO)
    # the gather fused with the interaction: two scopes, so mixed
    assert tab["mixed"] == {
        "fusion.1": ["tffm.fm_interaction_fwd", "tffm.row_gather"]}
    scopes = tab["scopes"]
    # a bitcast of a parameter and a constant carry another scope; the
    # fusion's work is the loss's alone
    assert "fusion.2" in scopes["tffm.loss"]
    # a Mosaic call, by its name
    assert "tffm.apply_write.2" in scopes["tffm.apply_write"]
    # the loop's body and condition, and a branch, as operations of
    # their own; the innermost scope wins
    assert {"add.2", "fusion.4", "lt.1"} <= set(scopes["tffm.apply_write"])
    assert scopes["tffm.exchange_merge"] == ["neg.1"]
    assert tab["containers"] == ["while.1", "conditional.1"]
    # what the compiler made, listed apart under ``moved``: the layout
    # copy of an argument takes its user's scope, the asynchronous copy
    # its operand's, a branch's copy of its parameter the scope of the
    # instruction that runs it
    assert tab["moved"] == {"tffm.row_gather": ["copy.1"],
                            "tffm.loss": ["copy-start.1", "copy-done.1"],
                            "tffm.exchange": ["copy.9"]}
    assert "tffm.exchange" not in scopes
    # program code outside every scope is unnamed; fused members,
    # parameters, constants and tuples are not operations of their own
    listed = ([n for part in ("scopes", "moved") for v in tab[part].values()
               for n in v] + list(tab["mixed"]) + tab["containers"])
    assert len(listed) == len(set(listed))  # each instruction once
    for name in ("add.9", "mul.1", "add.1", "gather.1", "multiply.2",
                 "Arg_0.1", "c.0", "tuple.0", "gte.9", "tuple.9"):
        assert name not in listed, name


def _write(path, lines):
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    return str(path)


def _libsvm(path, n, vocab, f, fields=False, seed=0):
    rng = np.random.default_rng(seed)
    lines = []
    for _ in range(n):
        ids = rng.choice(vocab, size=f, replace=False)
        toks = [(f"{j}:" if fields else "")
                + f"{i}:{rng.uniform(0.1, 1):.3f}" for j, i in enumerate(ids)]
        lines.append(f"{rng.integers(0, 2)} " + " ".join(toks))
    return _write(path, lines)


# What each path's step is made of: every part of it named.
ONE_DEVICE = {"tffm.row_gather", "tffm.loss", "tffm.apply_prep",
              "tffm.apply_write", "tffm.health"}
CASES = {
    "fm_scatter": (dict(), ONE_DEVICE | {
        "tffm.fm_interaction_fwd", "tffm.fm_interaction_bwd"}),
    "ftrl_scatter": (dict(optimizer="ftrl", ftrl_l1=1e-3, ftrl_l2=1e-3),
                     ONE_DEVICE | {"tffm.fm_interaction_fwd",
                                   "tffm.fm_interaction_bwd"}),
    "ffm": (dict(field_num=4), ONE_DEVICE | {
        "tffm.ffm_interaction_fwd", "tffm.ffm_interaction_bwd"}),
    "shardmap_2x2": (
        dict(lookup="shardmap", sparse_exchange="entries", mesh_data=2,
             mesh_model=2, vocabulary_size=8192, batch_size=512),
        {"tffm.row_gather", "tffm.fm_interaction_fwd",
         "tffm.fm_interaction_bwd", "tffm.loss", "tffm.exchange",
         "tffm.exchange_merge", "tffm.apply_write", "tffm.health"}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_the_real_step_is_named_and_recorded(case, tmp_path, monkeypatch):
    over, expected = CASES[case]
    vocab = over.get("vocabulary_size", 2048)
    fields = "field_num" in over
    data = _libsvm(tmp_path / "train.txt", 2 * over.get("batch_size", 64),
                   vocab, 4 if fields else 8, fields=fields)
    cfg = FmConfig(**{**dict(
        vocabulary_size=vocab, factor_num=4, max_features=8,
        batch_size=64, optimizer="adagrad", sparse_update=True,
        sparse_apply="scatter", train_files=[data],
        model_file=str(tmp_path / "model"), epoch_num=1, log_steps=0,
        thread_num=1, steps_per_dispatch=1,
        metrics_file=str(tmp_path / "metrics.jsonl"),
    ), **over})
    # one device where the case is one device's (the tests see eight)
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    if case == "shardmap_2x2":
        # the merged stream through the stream writer, as on a chip
        # (interpreted, the exchange keeps the row-major K2 otherwise)
        monkeypatch.setattr(sparse_apply, "exchange_takes_stream",
                            lambda data_shards: data_shards > 1)
        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                    (mesh_lib.DATA_AXIS, mesh_lib.MODEL_AXIS))
    Trainer(cfg, mesh=mesh).train()
    with open(cfg.metrics_file) as f:
        records = [json.loads(line) for line in f if line.strip()]
    compiles = [r for r in records if r.get("record") == "compile"]
    assert compiles and all("scopes" in r for r in compiles)
    rec = compiles[0]
    # the CPU's compiler fuses small parts with their neighbours: a scope
    # may be named by a mixed fusion alone
    named = set(rec["scopes"]).union(*rec["mixed"].values())
    assert named >= expected, (expected - named, rec["scopes"].keys())
    assert rec["scope_table_s"] >= 0
    listed = ([n for part in ("scopes", "moved") for v in rec[part].values()
               for n in v] + list(rec["mixed"]) + rec["containers"])
    assert len(listed) == len(set(listed))
    for scopes in rec["mixed"].values():
        assert len(scopes) > 1 and set(scopes) <= set(
            expected) | {"tffm.apply_prep"}


@pytest.mark.parametrize("writer", ["stream", "scatter_loop"])
def test_ftrl_arithmetic_is_scoped_wherever_it_lands(monkeypatch, writer):
    """FTRL's own operations -- the square roots of n, the sign and the
    absolute value of z, the closed form's division -- carry a scope in
    the compiled one-device step, on the table's rows and on w0, in
    either writer and whether or not the compiler fused them with
    another part of the step."""
    import re
    from functools import partial

    from fast_tffm_tpu.data.libsvm import Batch
    from fast_tffm_tpu.models import fm
    from fast_tffm_tpu.train import sparse as sparse_lib

    monkeypatch.setattr(sparse_apply, "takes_stream",
                        lambda *a: writer == "stream")
    cfg = FmConfig(vocabulary_size=2048, factor_num=4, max_features=8,
                   batch_size=64, optimizer="ftrl", ftrl_l1=1e-3,
                   ftrl_l2=1e-3, sparse_update=True, sparse_apply="scatter")
    params = fm.init_params(jax.random.PRNGKey(0), cfg)
    opt = sparse_lib.init_sparse_opt_state(cfg, params)
    b, f = cfg.batch_size, cfg.max_features
    batch = Batch(labels=jnp.zeros((b,)), ids=jnp.zeros((b, f), jnp.int32),
                  vals=jnp.ones((b, f)), fields=jnp.zeros((b, f), jnp.int32),
                  weights=jnp.ones((b,)))
    text = jax.jit(partial(sparse_lib.sparse_step, cfg, health=True)).lower(
        params, opt, batch).compile().as_text()
    ops = re.findall(r"= \S+ (sqrt|sign|abs|divide)\(.*?op_name=\"([^\"]*)\"",
                     text)
    assert {op for op, _ in ops} >= {"sqrt", "sign", "abs", "divide"}
    unscoped = [(op, name) for op, name in ops
                if not scopes_lib._SCOPE.search(name)]
    assert not unscoped, unscoped


def _scoped(scope):
    """One program whose scope alone differs from its twin's."""
    def step(x):
        with jax.named_scope(scope):
            return jnp.tanh(x) * 3.0 + 1.0
    return jax.jit(step)


def test_a_cached_executable_carries_its_own_scopes(tmp_path, monkeypatch):
    """Compiled as the trainer compiles its step, the persistent cache's
    key keeps the metadata: the twin compiled second through the same
    directory is compiled anew and names its own scope, and the same
    program again is a hit.  Other compiles keep jax's key."""
    monkeypatch.setenv(platform.ENV_COMPILE_CACHE, str(tmp_path / "cc"))
    x = jnp.arange(8, dtype=jnp.float32)
    tables, events = [], []
    try:
        assert platform.enable_compile_cache()
        for scope in ("tffm.health", "tffm.loss", "tffm.loss"):
            before = platform.compile_cache_stats()
            with platform.metadata_in_cache_key():
                compiled = _scoped(scope).lower(x).compile()
            tables.append(scopes_lib.scope_table(compiled.as_text()))
            after = platform.compile_cache_stats()
            events.append((after["hits"] - before["hits"],
                           after["misses"] - before["misses"]))
    finally:
        platform.disable_compile_cache()
    assert [set(t["scopes"]) for t in tables] == [
        {"tffm.health"}, {"tffm.loss"}, {"tffm.loss"}]
    assert events == [(0, 1), (0, 1), (1, 0)]
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


class _Text:
    def __init__(self, text=None):
        self.text = text

    def as_text(self):
        if self.text is None:
            raise RuntimeError("no text")
        return self.text


def test_the_compile_record_carries_the_table_or_why_not(tmp_path):
    path = str(tmp_path / "metrics.jsonl")
    sentinel = obs.CompileSentinel()
    writer = obs.JsonlWriter(path)
    sentinel.set_writer(writer)
    sentinel.record(1.0, 4, True, compiled=_Text(HLO))
    sentinel.record(1.0, 4, True, compiled=_Text())
    sentinel.record(1.0, 4, True)
    writer.close()
    with open(path) as f:
        good, bad, none = [json.loads(line) for line in f]
    assert good["scopes"] == scopes_lib.scope_table(HLO)["scopes"]
    assert good["scope_table_s"] >= 0 and "scope_table_error" not in good
    assert bad["scope_table_error"] == "RuntimeError: no text"
    assert "scopes" not in bad
    assert "scopes" not in none and "scope_table_error" not in none
