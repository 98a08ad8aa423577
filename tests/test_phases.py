"""The round's phases inside the compiled program (telemetry/profiling.py
``PHASES`` / ``phase``; the scopes in core/runtime.py and core/client.py)
and the process-wide handle on the running executable
(``compilewatch.latest``): every phase a mode has shows in the compiled
round's ``op_name`` metadata, the innermost scope names an instruction,
switching the observability off leaves nothing under its phases, and the
benchmark's per-phase metrics read all of it through ``latest``."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from commefficient_tpu.config import FedConfig
from commefficient_tpu.core import FedRuntime
from commefficient_tpu.parallel import make_mesh
from commefficient_tpu.telemetry import compilewatch
from commefficient_tpu.telemetry.profiling import (MODEL_PHASES, PHASES,
                                                   phase)
from perfbench.harness import phase_reader, readers, spec

W, B, D_IN, D_OUT = 4, 4, 6, 3
OBSERVABILITY = ("fed_signals", "fed_layer_signals", "fed_client_stats")
OFF = dict(signals=False, client_stats=False, signal_groups="off")
MODES = {
    "sketch": dict(mode="sketch", error_type="virtual", k=5, num_rows=2,
                   num_cols=32, exact_num_cols=True),
    "uncompressed": dict(mode="uncompressed", error_type="none"),
    "true_topk": dict(mode="true_topk", error_type="virtual", k=5),
}
# the six per-phase metrics of the benchmark (perfbench/metrics/<name>.py)
METRICS = ("client_step_ms", "sketch_encode_ms", "server_tail_ms",
           "observability_ms", "byte_ledger_ms", "round_unnamed_ms")


def loss_fn(params, batch, mask):
    pred = batch["x"] @ params["w"]
    m = mask.astype(jnp.float32)
    err = ((pred - batch["y"]) ** 2).sum(axis=1)
    loss = (err * m).sum() / jnp.maximum(m.sum(), 1.0)
    return loss, (loss,)


class Recorder:
    def event(self, kind, **kw):
        pass


def make_runtime(mode, mesh=None, **kw):
    """The runtime of a tiny model, with the observability as shipped
    unless ``kw`` says otherwise."""
    cfg = FedConfig(**{**dict(
        local_momentum=0.0, virtual_momentum=0.9, weight_decay=0.0,
        num_workers=W, local_batch_size=B, track_bytes=True,
        num_clients=8, num_results_train=2, num_results_val=2),
        **MODES[mode], **kw})
    params = {"w": jnp.asarray(
        np.random.RandomState(0).randn(D_IN, D_OUT), jnp.float32)}
    return FedRuntime(cfg, params, loss_fn, num_clients=8, mesh=mesh)


def run_round(mode, mesh=None, lr=0.05, runtime=None, **kw):
    """One round through a watched runtime. Returns (runtime,
    {instruction: phase}) of the compiled round."""
    if runtime is None:
        runtime = make_runtime(mode, mesh=mesh, **kw)
        runtime.set_compile_watcher(compilewatch.JitWatcher(Recorder()))
    rng = np.random.RandomState(1)
    batch = {"x": jnp.asarray(rng.randn(W, B, D_IN), jnp.float32),
             "y": jnp.asarray(rng.randn(W, B, D_OUT), jnp.float32)}
    if mesh is not None:
        batch = jax.device_put(batch, runtime.batch_sharding())
    runtime.round(runtime.init_state(), jnp.arange(W, dtype=jnp.int32),
                  batch, jnp.ones((W, B), bool), lr)
    hlo = runtime.compile_watcher.executables["round_step"].as_text()
    return runtime, phase_reader.parse_hlo(hlo, PHASES)


def phases_of(table):
    return set(table.values()) - {None}


@pytest.mark.parametrize("mode", sorted(MODES))
def test_default_round_names_every_phase_of_its_mode(mode):
    _rt, table = run_round(mode)
    want = set(PHASES) - {"fed_table_reduce"}       # mesh only
    want -= set(MODEL_PHASES)      # this model has no such layers
    if mode != "sketch":
        want -= {"fed_sketch_encode"}
    assert phases_of(table) == want


@pytest.mark.parametrize("mode", sorted(MODES))
def test_observability_off_leaves_nothing_under_its_phases(mode):
    _rt, table = run_round(mode, **OFF)
    assert not phases_of(table) & set(OBSERVABILITY)
    assert {"fed_client_step", "fed_server_tail",
            "fed_byte_ledger"} <= phases_of(table)


def client_step_instructions(lowered):
    """(shape, opcode) of every instruction a lowering traced under
    ``fed_client_step``, sorted, names aside: those whose ``op_name`` path
    holds the scope, and those of the computations they call (an inner
    function's instructions are named from its own root). Broadcasts of
    constants are left out: the lowering shares one between its users,
    and the first user's scope names it."""
    text = lowered.as_text(dialect="hlo", debug_info=True)
    bodies, comp = {}, None          # computation -> its instruction lines
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY\s+)?([\w.\-]+)\s*\{\s*$", line)
        m = phase_reader._LINE.match(line)
        if head:
            comp = bodies.setdefault(head.group(1), [])
        elif m and comp is not None:
            comp.append(m.groups())
    todo = [ins for body in bodies.values() for ins in body
            if "/fed_client_step/" in ins[1]]
    under = {}                       # instruction name -> (shape, opcode)
    while todo:
        name, rest = todo.pop()
        if name not in under:
            under[name] = re.match(r"(.*?)\s([\w\-]+)\(", rest).groups()
            for called in phase_reader._CALLS.findall(rest):
                todo += bodies[called]
    return sorted(f for f in under.values()
                  if f[1] not in ("broadcast", "constant"))


@pytest.mark.parametrize("mode", sorted(MODES))
def test_round_and_cohort_trace_one_client_step(mode):
    """The round and the async cohort run one client half
    (``FedRuntime._client_half``): with the signals off in both (they are
    off under ``--async_agg``), what the cohort traces under the client
    step is what the round traces under it, instruction for
    instruction."""
    rt = make_runtime(mode, **OFF)
    rt_async = make_runtime(mode, async_agg=True, max_inflight=1,
                            buffer_goal=1, **OFF)
    batch = {"x": jnp.zeros((W, B, D_IN)), "y": jnp.zeros((W, B, D_OUT))}
    args = (jnp.arange(W, dtype=jnp.int32), batch, jnp.ones((W, B), bool),
            jnp.asarray(0.05, jnp.float32))
    in_round = client_step_instructions(
        rt._round.lower(rt.init_state(), *args, rt.cs))
    in_cohort = client_step_instructions(
        rt_async._cohort.lower(rt_async.init_state(), *args, rt_async.cs))
    assert len(in_round) > 20
    assert in_cohort == in_round
    opcodes = {op for _shape, op in in_round}
    assert "dot" in opcodes and "while" in opcodes


def test_innermost_scope_names_the_instruction():
    # the fused encode runs inside the client step's scan: both names are
    # in the path, and the encode's instructions are the encode's
    rt, _table = run_round("sketch")
    hlo = rt.compile_watcher.executables["round_step"].as_text()
    nested = [ln for ln in hlo.splitlines()
              if "fed_client_step/" in ln and "/fed_sketch_encode/" in ln
              and phase_reader._LINE.match(ln)]
    assert nested
    table = phase_reader.parse_hlo("\n".join(nested), PHASES)
    assert set(table.values()) == {"fed_sketch_encode"}
    # and a reader that was not given the inner phase falls to the outer
    outer = phase_reader.parse_hlo("\n".join(nested), ("fed_client_step",))
    assert set(outer.values()) == {"fed_client_step"}


def test_unknown_phase_raises():
    with pytest.raises(ValueError, match="nonsense"):
        phase("nonsense")
    assert len(set(PHASES)) == len(PHASES) == 12
    assert all(p.startswith("fed_") for p in PHASES)
    with phase("fed_signals"):                    # a known one is a scope
        pass


@pytest.mark.parametrize("family,config,want", [
    ("joyai_moe", "joyai_flash_share32", set(MODEL_PHASES)),
    ("laguna_moe", "laguna_xs2_share32", {"fed_attention", "fed_moe"}),
])
def test_model_phases_are_in_the_rounds_of_the_models_that_have_them(
        family, config, want):
    """A dense round of each config-built language model at its
    configuration file's rehearsal sizes: latent attention's glue and the
    prediction module name their instructions in the JoyAI round and in
    no other's (the default model's: the test above)."""
    import importlib
    import json
    fam = importlib.import_module(f"perfbench.families.{family}")
    with open(spec.config_path(config)) as f:
        hf = json.load(f)
    cfg = fam.parse(["--mode", "uncompressed", "--error_type", "none",
                     "--local_momentum", "0", "--weight_decay", "0",
                     "--lm_chunk", "8", "--num_candidates", "1",
                     "--max_seq_len", "32", "--compute_dtype", "float32",
                     "--num_workers", "2", "--local_batch_size", "1",
                     "--microbatch_size", "1", "--remat"])
    b = fam.build(cfg, {**hf, **hf["rehearse"]}, 0)
    rt = FedRuntime(cfg.replace(num_clients=2), b.params, b.loss_fn,
                    num_clients=2)
    rt.set_compile_watcher(compilewatch.JitWatcher(Recorder()))
    batch = {k: v[:2, None] for k, v in b.dataset.arrays.items()}
    rt.round(rt.init_state(), np.arange(2), batch, np.ones((2, 1), bool),
             0.05)
    table = phase_reader.parse_hlo(
        rt.compile_watcher.executables["round_step"].as_text(), PHASES)
    assert phases_of(table) & set(MODEL_PHASES) == want
    by_phase = {p: sum(v == p for v in table.values()) for p in want}
    assert min(by_phase.values()) >= 10, by_phase


def test_latest_is_the_executable_that_ran_and_a_recompile_replaces_it():
    rt, _ = run_round("uncompressed")
    first = compilewatch.latest("round_step")
    assert first is rt.compile_watcher.executables["round_step"]
    assert compilewatch.latest("no_such_step") is None
    # a per-parameter lr vector is a new signature: the round recompiles
    run_round("uncompressed", lr=np.full(D_IN * D_OUT, 0.05), runtime=rt)
    second = compilewatch.latest("round_step")
    assert second is not first
    assert second is rt.compile_watcher.executables["round_step"]
    assert rt.compile_watcher.n_compiles == 2


def test_mesh_round_has_the_table_reduce_on_its_collectives():
    if len(jax.devices()) < 4:
        pytest.skip("needs four virtual devices")
    mesh = make_mesh((4,), ("clients",))
    rt, table = run_round("sketch", mesh=mesh)
    hlo = rt.compile_watcher.executables["round_step"].as_text()
    # the table's reduce-scatter and the datum count's all-reduce, both
    # inside the client step's shard_map: the inner name wins
    opcodes = set()
    for line in hlo.splitlines():
        m = phase_reader._LINE.match(line)
        if m and table[m.group(1)] == "fed_table_reduce":
            found = re.findall(
                r" (reduce-scatter|all-reduce|all-to-all)\(", m.group(2))
            assert not found or "fed_client_step/" in line
            opcodes |= set(found)
    assert {"reduce-scatter", "all-reduce"} <= opcodes


def drawn_ctx(table):
    """A trace in the reducer's form with one 1,000 ns leaf event per
    instruction of the round and one event of another executable."""
    selfs = [(name, 1000, True, i * 1000, (i + 1) * 1000, "")
             for i, name in enumerate(sorted(table))]
    n = len(selfs)
    selfs.append(("other_program_gather.1", 500, True, n * 1000,
                  n * 1000 + 500, ""))
    return {"trace": {"chips": {0: {"selfs": selfs}}}, "traced_rounds": 2}


@pytest.mark.parametrize("off", [False, True], ids=["shipped", "off"])
def test_phase_metrics_partition_the_round_and_observability_reads_zero(off):
    _rt, table = run_round("sketch", **(OFF if off else {}))
    ctx = drawn_ctx(table)
    got = {}
    for name in METRICS:
        metric = spec._load(spec.metric_path(name))
        got[name] = readers.read(metric, ctx)        # through latest()
    assert None not in got.values()
    total_ms = sum(t[1] for t in ctx["trace"]["chips"][0]["selfs"]) / 2e6
    assert sum(got.values()) == pytest.approx(total_ms)
    assert got["round_unnamed_ms"] >= 500 / 2e6
    assert got["client_step_ms"] > 0 and got["sketch_encode_ms"] > 0
    assert got["server_tail_ms"] > 0 and got["byte_ledger_ms"] > 0
    if off:
        assert got["observability_ms"] == 0.0
    else:
        assert got["observability_ms"] > 0


def test_watched_executables_key_the_compile_cache_on_their_metadata():
    """The phase names live in instruction metadata, which JAX strips
    from the persistent cache's key by default: a cache shared with a
    checkout that names them otherwise would hand back stale names."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    seen = []

    class Lowered:
        def compile(self):
            seen.append(getattr(jax.config, flag))
            return lambda *args: args

    class Fn:
        def lower(self, *args):
            return Lowered()

    before = getattr(jax.config, flag)
    watcher = compilewatch.JitWatcher(Recorder())
    assert watcher.wrap("some_step", Fn())(1, 2) == (1, 2)
    assert seen == [True]
    assert getattr(jax.config, flag) == before
    assert compilewatch.latest("some_step") is not None
