"""The cell ``joyai_sketch_8x1x4096`` rehearsed on the CPU: the same code
at the configuration's ``rehearse`` sizes (hidden 64, 5 layers and the
prediction module, 4 of 16 experts held, heads of 24 and 16). It proves
nothing about the chip and prints no result line; it shows that the
family, its plain reference, the traffic file and the five new metric
readers hang together."""

import importlib.util
import json

from perfbench.tests.test_rehearse import _run

CELL = "joyai_sketch_8x1x4096"
PHASE_METRICS = ("mla_latent_ms", "mtp_ms", "mla_attention_ms", "mla_moe_ms")


def test_the_joyai_cell_rehearses_with_its_new_metrics():
    p = _run("--workload", CELL, "--seed", "3800000501", "--seconds", "1",
             "--trace", "1", "--rehearse")
    assert p.returncode == 5, p.stderr[-2000:]
    assert "family joyai_moe: d = 336432" in p.stdout
    line = [ln for ln in p.stdout.splitlines()
            if "rehearsal result" in ln][0]
    result = json.loads(line.split("(NOT a measurement):", 1)[1])
    assert result["correct"] is True, p.stdout[-3000:]
    # the phases exist in the compiled round and hold device time; the
    # kernels' rooflines have nothing to read off the TPU and are left out
    for name in PHASE_METRICS:
        assert result["metrics"][name]["value"] > 0, name
    assert "splash_mla_roofline" not in result["metrics"]
    # Laguna's twins are conditioned on its family and stay out
    assert "attention_ms" not in result["metrics"]


def test_no_chip_exits_before_building_the_model():
    p = _run("--workload", CELL, "--seed", "1", "--seconds", "1",
             "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3, p.stderr[-2000:]
    assert "family" not in p.stdout


def _metric(name):
    from perfbench.harness import spec
    s = importlib.util.spec_from_file_location(
        name, spec.metric_path(name, "py"))
    m = importlib.util.module_from_spec(s)
    s.loader.exec_module(m)
    return m


def test_the_kernels_work_is_counted_from_the_cells_files():
    from perfbench.families.joyai_moe import cell_shapes
    facts = {"config": "joyai_flash_share32", "traffic": "sketch_8x1x1x4096"}
    config, S, sequences = cell_shapes(facts)
    assert (S, sequences) == (4096, 8)
    att = _metric("splash_mla_roofline")
    assert att.kept_blocks(4096) == 36         # of 64: the causal half
    flops, bytes_ = att.attention_work(config, S, sequences)
    # 6 blocks (the prediction module's is one) x 32 heads x 36 blocks x 8
    # sequences of 2 x 512^2 x (4 x 192 + 3 x 128) at the published widths
    assert flops == 6 * 32 * 36 * 8 * 2 * 512 * 512 * 1152
    assert 3.3e13 < flops < 3.4e13 and bytes_ < 2e10
    assert att.KERNEL.match("splash_mqa_fwd_residuals.3")
    assert att.KERNEL.match("splash_mha_dkv_no_residuals")
    assert not att.KERNEL.match("circulant_sketch_encode")


def test_a_reader_finds_nothing_in_a_program_without_the_phases(monkeypatch):
    """On the parent commit, which names no ``fed_latent`` or ``fed_mtp``,
    the new readers return nothing and do not raise."""
    from perfbench.harness import phase_reader
    monkeypatch.setattr(phase_reader, "program_phases", lambda: (
        "fed_client_step", "fed_attention", "fed_moe"))
    ctx = {"trace": {"chips": {0: {"selfs": []}}}, "traced_rounds": 1,
           "peaks": None}
    assert _metric("mla_latent_ms").read(ctx) is None
    assert _metric("mtp_ms").read(ctx) is None
    assert _metric("splash_mla_roofline").read(ctx) is None
    assert _metric("splash_mla_roofline").read({"trace": None}) is None
