"""What PR 21 (bring-up on the v5e) added that a CPU can check: where the
compile cache goes, that an unknown device is an error, that chip_smoke.py
refuses without a chip, that a sketch says which path it takes, and that an
aborted run is a failed process while a drained preemption is not."""

import os
import subprocess
import sys
import types

import jax
import pytest

from commefficient_tpu import config, cv_train
from commefficient_tpu.core import PreemptGuard
from commefficient_tpu.ops.circulant import make_circulant_sketch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_dir_calls(monkeypatch):
    """Record jax.config.update calls instead of performing them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    return calls


def test_cache_dir_from_environment_is_left_alone(monkeypatch, tmp_path,
                                                  cache_dir_calls):
    """JAX_COMPILATION_CACHE_DIR set: the operator placed the cache; the
    code sets nothing, whatever --compile_cache says."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    for requested in (config.DEFAULT_COMPILATION_CACHE_DIR,
                      str(tmp_path / "flag"), ""):
        assert (config.enable_compilation_cache_dir(requested)
                == str(tmp_path / "env"))
    assert cache_dir_calls == []
    assert not (tmp_path / "flag").exists()
    # the operator's directory is made if it is not there yet: JAX does
    # not make it, and every write to it would fail with a warning
    assert (tmp_path / "env").is_dir()


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch, cache_dir_calls):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    default = config.FedConfig().compilation_cache_dir
    assert default == os.path.join(REPO, ".jax_cache")
    assert config.enable_compilation_cache_dir(default) == default
    assert ("jax_compilation_cache_dir", default) in cache_dir_calls


def test_cache_dir_empty_is_off_and_unwritable_is_an_error(
        monkeypatch, tmp_path, cache_dir_calls):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert config.enable_compilation_cache_dir("") is None
    assert cache_dir_calls == []
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    with pytest.raises(OSError):
        config.enable_compilation_cache_dir(str(blocker / "cache"))


def test_bench_peaks_raise_on_unknown_device():
    import bench_common
    v5e = types.SimpleNamespace(device_kind="TPU v5 lite")
    assert bench_common.peak_flops(v5e) == 197e12
    assert bench_common.peak_hbm_gbps(v5e) == 819.0
    cpu = types.SimpleNamespace(device_kind="cpu")
    with pytest.raises(ValueError, match="unknown device kind 'cpu'"):
        bench_common.peak_flops(cpu)
    with pytest.raises(ValueError, match="unknown device kind 'cpu'"):
        bench_common.peak_hbm_gbps(cpu)


def test_chip_smoke_refuses_without_a_chip(tmp_path):
    """Under JAX_PLATFORMS=cpu the script exits non-zero before it builds
    a model or writes anything, and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(REPO, "chip_smoke.py")],
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode == 4, (p.returncode, p.stderr[-2000:])
    assert "no TPU" in p.stdout and '"ok"' not in p.stdout
    assert os.listdir(tmp_path) == []


def test_sketch_says_which_path_it_takes():
    """On the CPU every sketch takes the XLA path and names the reason;
    --pallas on only becomes an error where the kernels could run."""
    for policy in ("auto", "on", "off"):
        cs = make_circulant_sketch(d=9000, c=2048, r=3, pallas=policy)
        assert cs.kernel_path == "xla"
        assert not cs._pallas_eligible()
    assert make_circulant_sketch(9000, 2048, 3,
                                 pallas="off").pallas_blocker() == \
        "--pallas off"
    assert "backend is 'cpu'" in make_circulant_sketch(
        9000, 2048, 3).pallas_blocker()


def test_pallas_on_raises_where_the_kernels_could_run(monkeypatch):
    """On the TPU backend, `on` with an ineligible geometry is an error
    that names the failed condition (here: an unaligned column count)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="num_cols=500 is not a multiple"):
        make_circulant_sketch(d=9000, c=500, r=3, pallas="on")
    assert make_circulant_sketch(d=9000, c=500, r=3,
                                 pallas="auto").kernel_path == "xla"
    assert make_circulant_sketch(d=9000, c=2048, r=3,
                                 pallas="on").kernel_path == "pallas"


def test_aborted_run_is_a_failed_process_but_a_drain_is_not():
    """finish_run is what both drivers' main() end in, and the root
    cv_train.py / gpt2_train.py are main() and nothing else: a run that
    returned no summary exits non-zero unless it was preempted."""
    seen = []
    on_finish = lambda *a: seen.append(a)  # noqa: E731
    with pytest.raises(SystemExit) as exc:
        cv_train.finish_run(None, PreemptGuard(), on_finish, "rt", "state")
    assert exc.value.code not in (0, None) and seen == []
    drained = PreemptGuard()
    drained.request("SIGTERM")
    cv_train.finish_run(None, drained, on_finish, "rt", "state")
    cv_train.finish_run({"epoch": 1}, PreemptGuard(), on_finish, "rt", "st")
    assert seen == [("rt", "state", None), ("rt", "st", {"epoch": 1})]
