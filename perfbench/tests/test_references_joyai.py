"""Family ``joyai_moe``: its plain reference against the program at the
configuration file's rehearsal sizes on the CPU, with the deliberately
wrong variants that must fail the tolerance; the lint on the tree."""

import json
import os
import subprocess
import sys

import pytest

from perfbench.tests.conftest import ROOT


def _built(dtype="float32", seed=0):
    from perfbench.families import joyai_moe as fam
    from perfbench.harness import spec
    with open(spec.config_path("joyai_flash_share32")) as f:
        config = json.load(f)
    cfg = fam.parse(["--weight_decay", "0", "--lm_chunk", "8",
                     "--num_candidates", "1", "--max_seq_len", "32",
                     "--compute_dtype", dtype, "--microbatch_size", "1",
                     "--local_batch_size", "1", "--remat"])
    return fam, fam.build(cfg, {**config, **config["rehearse"]}, seed), cfg


@pytest.mark.parametrize("variant,ok", [
    (None, True), ("bf16", False), ("no_mtp", False),
    ("bias_in_weights", False)])
def test_joyai_moe_reference(variant, ok):
    """The harness's comparison perturbs the zero selection biases by
    0.02, so which experts are chosen depends on them; a reference with
    bfloat16 operands, without the second loss, or with the bias in the
    weights is outside the float32 tolerance."""
    from perfbench.harness import checks
    fam, built, cfg = _built()
    out = checks.model_step(fam, built, cfg, seed=3, variant=variant, n=1)
    assert out["ok"] is ok, out
    if not ok:
        assert (out["grad_rel_l2"] > 5 * out["tol"]["grad_rel_l2"]
                or out["loss_rel"] > 5 * out["tol"]["loss_rel"]), out


def test_the_reference_calls_nothing_of_the_program():
    path = os.path.join(ROOT, "perfbench", "families",
                        "joyai_moe_reference.py")
    with open(path) as f:
        source = f.read()
    assert "commefficient_tpu" not in source.split('"""', 2)[2]
    assert "import perfbench" not in source and "from perfbench" not in source


def test_the_lint_reports_what_it_reported_before_the_cell():
    """``perfbench/lint.py`` matches ``hidden`` inside ``num_hidden_layers``
    and reads the key as a width (PERF.md section 7: for a ``benchmark``
    PR): one such line a configuration that cuts its depth, the Laguna
    file's on the parent, and nothing else."""
    p = subprocess.run([sys.executable,
                        os.path.join(ROOT, "perfbench", "lint.py")],
                       cwd=ROOT, capture_output=True, text=True)
    lines = [ln for ln in (p.stdout + p.stderr).splitlines()
             if ln.startswith("lint:") and "problem(s)" not in ln]
    assert all("reduced names a width: 'num_hidden_layers'" in ln
               for ln in lines), lines
    assert len(lines) <= 2, lines
