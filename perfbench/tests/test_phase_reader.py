"""The join instruction name -> phase on an HLO text written by hand, and
the per-phase sums on a trace small enough to draw.

Two chips, times in ns, window 0..1000 (``bench:stretch``). Chip 0:

    while.1 (client step)     100 ......................... 500   (parent)
      fusion.2  (client step)   100 .. 200
      copy-done.1 (no metadata, 200..250: in the while's body, so its)
      encode.3  (encode, nested)        250 ..... 400
    fusion.4  (server tail)                             500 .. 600
    reduce.5  (signals)                                 600 .. 650
    sort.6    (client stats)                            650 .. 700
    scatter.7 (byte ledger)                             700 .. 720
    copy.8    (in the HLO, no metadata)                 720 .. 750
    gather.99 (another executable)                      800 .. 900

self: while.1 = 400 - 100 - 50 - 150 = 100, so client step 250, encode 150, tail
100, observability 100, byte ledger 20, unnamed 30 + 100 = 130: 750 = busy.
Chip 1 is chip 0 with fusion.4 twice as long (500..700 covers reduce.5 and
sort.6, which then are its children: tail 100 there too, but busy the same);
so every metric takes chip 0's value or the same.
"""

import pytest

from perfbench.harness import phase_reader, tracered

PHASES = ("fed_client_step", "fed_sketch_encode", "fed_table_reduce",
          "fed_server_tail", "fed_signals", "fed_layer_signals",
          "fed_client_stats", "fed_byte_ledger")

HLO = '''HloModule jit__round_step, entry_computation_layout={(f32[8]{0})->f32[8]{0}}

%fused_computation.2 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  ROOT %multiply.9 = f32[8]{0} multiply(%param_0, %param_0), metadata={op_name="jit(_round_step)/fed_client_step/while/body/transpose(jvp(mul))" source_file="client.py" source_line=380}
}

%body.3 (p.1: (s32[], f32[8])) -> (s32[], f32[8]) {
  %p.1 = (s32[], f32[8]{0}) parameter(0)
  %copy-start.1 = (f32[8]{0:S(1)}, f32[8]{0}, u32[]) copy-start(%gte.2)
  %copy-done.1 = f32[8]{0:S(1)} copy-done(%copy-start.1)
  ROOT %tuple.2 = (s32[], f32[8]{0}) tuple(%gte.1, %copy-done.1)
}

%add.5 (x.1: f32[], y.1: f32[]) -> f32[] {
  %x.1 = f32[] parameter(0)
  %y.1 = f32[] parameter(1)
  ROOT %add.6 = f32[] add(%x.1, %y.1)
}

ENTRY %main.10 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0), metadata={op_name="state.ps_weights"}
  %while.1 = (s32[], f32[8]{0}) while(%tuple.0), condition=%cond.4, body=%body.3, metadata={op_name="jit(_round_step)/fed_client_step/while" source_file="client.py" source_line=411}
  %fusion.2 = f32[8]{0} fusion(%Arg_0.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(_round_step)/fed_client_step/while/body/transpose(jvp(mul))"}
  encode.3 = f32[2,4]{1,0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(_round_step)/fed_client_step/while/body/fed_sketch_encode/jit(encode)/pallas_call"}
  %fusion.4 = f32[8]{0} fusion(%encode.3), kind=kLoop, calls=%fc.4, metadata={op_name="jit(_round_step)/fed_server_tail/jit(topk)/fed_unknown_scope/sort"}
  %reduce.5 = f32[] reduce(%fusion.4, %c), dimensions={0}, to_apply=%add.5, metadata={op_name="jit(_round_step)/fed_signals/reduce_sum"}
  %reduce.9 = f32[] reduce(%fusion.4, %c), dimensions={0}, to_apply=%add.5, metadata={op_name="jit(_round_step)/fed_server_tail/reduce_sum"}
  %sort.6 = f32[4]{0} sort(%l), dimensions={0}, metadata={op_name="jit(_round_step)/fed_client_stats/jit(sort)/sort"}
  %scatter.7 = s32[8]{0} scatter(%a, %b, %c), metadata={op_name="jit(_round_step)/fed_byte_ledger/scatter"}
  %copy.8 = f32[8]{0} copy(%fusion.4)
  ROOT %tuple.11 = (f32[8]{0}) tuple(%copy.8), metadata={op_name="jit(_round_step)/fed_server_tail/sub"}
}
'''


def chip(name, tail_end):
    return {"name": name, "lines": [{"name": "XLA Ops", "events": [
        ["while.1", 100, 400, "op=while"],
        ["fusion.2", 100, 100, "op=fusion"],
        ["copy-done.1", 200, 50, "op=copy-done"],
        ["encode.3", 250, 150, "op=custom-call"],
        ["fusion.4", 500, tail_end - 500, "op=fusion"],
        ["reduce.5", 600, 50, "op=reduce"],
        ["sort.6", 650, 50, "op=sort"],
        ["scatter.7", 700, 20, "op=scatter"],
        ["copy.8", 720, 30, "op=copy"],
        ["gather.99", 800, 100, "op=gather"]]}]}


TRACE = {"planes": [
    chip("/device:TPU:0", 600), chip("/device:TPU:1", 700),
    {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench:stretch", 0, 1000, ""]]}]}]}

SIX = {"client_step_ms": ("fed_client_step",),
       "sketch_encode_ms": ("fed_sketch_encode",),
       "server_tail_ms": ("fed_server_tail",),
       "observability_ms": ("fed_signals", "fed_layer_signals",
                            "fed_client_stats"),
       "byte_ledger_ms": ("fed_byte_ledger",),
       "round_unnamed_ms": None}


def test_parser_reads_names_phases_and_the_innermost_scope():
    table = phase_reader.parse_hlo(HLO, PHASES)
    assert table["while.1"] == "fed_client_step"
    assert table["fusion.2"] == "fed_client_step"     # transpose(jvp(..))
    assert table["multiply.9"] == "fed_client_step"   # ROOT of a fusion
    assert table["encode.3"] == "fed_sketch_encode"   # no %, nested: inner
    # a fed_* token that is no phase of the program is not a phase
    assert table["fusion.4"] == "fed_server_tail"
    assert table["sort.6"] == "fed_client_stats"      # jit(sort) is no scope
    assert table["tuple.11"] == "fed_server_tail"     # ROOT, % prefix
    # no metadata: the phase of what calls the computation it is in ...
    assert table["copy-done.1"] == table["tuple.2"] == "fed_client_step"
    assert table["copy.8"] is None        # ... and the entry has no caller
    assert table["add.6"] is None         # two callers that disagree
    assert table["Arg_0.1"] is None                   # metadata, no phase
    assert "gather.99" not in table and "HloModule" not in table
    # a reader that is not given the inner phase falls to the outer one
    outer = phase_reader.parse_hlo(HLO, ("fed_client_step",))
    assert outer["encode.3"] == "fed_client_step"
    assert outer["fusion.4"] is None


def test_the_six_metrics_add_up_to_busy_on_the_worst_chip():
    table = phase_reader.parse_hlo(HLO, PHASES)
    trace = tracered.reduce(TRACE)
    ctx = {"trace": trace, "traced_rounds": 1}
    got = {name: phase_reader.phase_ms(ctx, phases, table)
           for name, phases in SIX.items()}
    ns = {k: round(v * 1e6) for k, v in got.items()}
    assert ns == {"client_step_ms": 250, "sketch_encode_ms": 150,
                  "server_tail_ms": 100, "observability_ms": 100,
                  "byte_ledger_ms": 20, "round_unnamed_ms": 130}
    assert sum(ns.values()) == 750
    assert abs(trace["chips"][0]["busy_s"] - 750e-9) < 1e-15
    # per round, as readers._per_round_ms
    ctx2 = {"trace": trace, "traced_rounds": 2}
    assert phase_reader.phase_ms(ctx2, SIX["client_step_ms"], table) == (
        pytest.approx(got["client_step_ms"] / 2))


def test_without_a_trace_or_a_table_nothing_is_reported(monkeypatch):
    from commefficient_tpu.telemetry import compilewatch
    table = phase_reader.parse_hlo(HLO, PHASES)
    assert phase_reader.phase_ms({"trace": None}, None, table) is None
    ctx = {"trace": tracered.reduce(TRACE), "traced_rounds": 1}
    # nothing compiled yet in this process
    monkeypatch.setattr(compilewatch, "LATEST", {}, raising=False)
    assert phase_reader.phase_ms(ctx, None) is None
    # a program from before the scopes: no latest(), no PHASES; no raise
    monkeypatch.delattr(compilewatch, "latest", raising=False)
    assert phase_reader.round_table() is None
    assert phase_reader.phase_ms(ctx, ("fed_client_step",)) is None


def test_the_running_executable_is_parsed_once(monkeypatch):
    from commefficient_tpu.telemetry import compilewatch

    class Exe:
        calls = 0

        def as_text(self):
            Exe.calls += 1
            return HLO

    monkeypatch.setattr(compilewatch, "LATEST", {"round_step": Exe()})
    ctx = {"trace": tracered.reduce(TRACE), "traced_rounds": 1}
    got = [phase_reader.phase_ms(ctx, phases) for phases in SIX.values()]
    assert Exe.calls == 1
    assert round(sum(got) * 1e6) == 750
    # a recompile replaces the executable, and the table with it
    monkeypatch.setattr(compilewatch, "LATEST", {"round_step": Exe()})
    phase_reader.phase_ms(ctx, None)
    assert Exe.calls == 2


def test_the_table_as_the_command_prints_it(tmp_path, capsys):
    import json
    dump, hlo = tmp_path / "dump.json", tmp_path / "hlo.txt"
    dump.write_text(json.dumps(TRACE))
    hlo.write_text(HLO)
    assert phase_reader.main([str(dump), str(hlo), "--rounds", "1"]) == 0
    out = capsys.readouterr().out.splitlines()
    assert "self times add up to 0.001" in out[0]
    rows = {ln.split()[0]: ln for ln in out[1:]}
    assert set(PHASES) <= set(rows)
    assert "encode.3" in rows["fed_sketch_encode"]
    assert "gather.99" in "\n".join(out)          # not in the round's HLO
    assert "copy.8" in "\n".join(out)             # in it, without a phase
