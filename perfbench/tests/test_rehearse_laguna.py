"""The cell ``laguna_sketch_8x1x4096`` rehearsed on the CPU: the same code
at the configuration's ``rehearse`` sizes (hidden 64, 5 layers, 4 of 16
experts held, window 8). It proves nothing about the chip and prints no
result line; it shows that the family, its plain reference, the traffic
file and the new metric readers hang together."""

import json

from perfbench.tests.test_rehearse import _run

CELL = "laguna_sketch_8x1x4096"


def test_the_laguna_cell_rehearses_with_its_new_metrics():
    p = _run("--workload", CELL, "--seed", "2800000501", "--seconds", "1",
             "--trace", "1", "--rehearse")
    assert p.returncode == 5, p.stderr[-2000:]
    assert "family laguna_moe: d = 260416" in p.stdout
    line = [ln for ln in p.stdout.splitlines()
            if "rehearsal result" in ln][0]
    result = json.loads(line.split("(NOT a measurement):", 1)[1])
    assert result["correct"] is True, p.stdout[-3000:]
    # the phases exist in the compiled round and hold device time; the
    # kernels' rooflines have nothing to read off the TPU and are left out
    assert result["metrics"]["attention_ms"]["value"] > 0
    assert result["metrics"]["moe_ms"]["value"] > 0
    assert "splash_mqa_roofline" not in result["metrics"]


def test_no_chip_exits_before_building_the_model():
    p = _run("--workload", CELL, "--seed", "1", "--seconds", "1",
             "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert p.returncode == 3, p.stderr[-2000:]
    assert "family" not in p.stdout


def test_the_kernels_work_is_counted_from_the_cells_files():
    import importlib.util
    from perfbench.families.laguna_moe import cell_shapes
    from perfbench.harness import spec
    facts = {"config": "laguna_xs2_share32", "traffic": "sketch_8x1x1x4096"}
    config, S, sequences = cell_shapes(facts)
    assert (S, sequences) == (4096, 8)

    def load(name):
        s = importlib.util.spec_from_file_location(
            name, spec.metric_path(name, "py"))
        m = importlib.util.module_from_spec(s)
        s.loader.exec_module(m)
        return m

    att = load("splash_mqa_roofline")
    # 8 x 8 blocks of 512: the causal half with its diagonal, and a band
    # of two on a window of 512
    assert att.kept_blocks(4096, None) == 36
    assert att.kept_blocks(4096, 512) == 15
    flops, _ = att.attention_work(config, S, sequences)
    per_block = 7 * 2 * 512 * 512 * 128
    assert flops == 8 * per_block * (2 * 48 * 36 + 3 * 64 * 15)
