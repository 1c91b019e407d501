"""CPU rehearsal of ``chip_smoke.py``: its core runs end to end at smoke
width (Pallas kernels in interpret mode), and its entry point refuses to run
without a TPU."""

import os
import shutil
import subprocess
import sys

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
import jax  # noqa: E402
from repro.configs import get_smoke_config  # noqa: E402
from repro.launch.serve import enable_compile_cache  # noqa: E402


def test_core_serves_and_checks_at_smoke_width():
    lines = []
    res = chip_smoke.run(
        get_smoke_config(chip_smoke.ARCH), batch=2, max_len=384,
        prompt_lens=(128, 256), n_requests=4, new_tokens=4, check_len=128,
        log=lines.append,
    )
    assert res["served"] == 4
    assert res["free_pages"] == res["pool_pages"]
    assert set(res["kernel"]) == {"decode", "prefill[128]", "prefill[256]"}
    assert res["decode_logit_err"] <= chip_smoke.LOGIT_TOL
    assert res["prefill_logit_err"] <= chip_smoke.LOGIT_TOL
    assert res["solo_matches"] == 4  # the ragged oracle holds on the kernel path
    assert any(line.startswith("compile decode:") for line in lines)


def test_main_refuses_without_tpu(monkeypatch, tmp_path, capsys):
    # a set cache directory makes the cache helper leave JAX's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert chip_smoke.main() != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "no TPU found" in out.err


def test_compile_cache_dir(monkeypatch, tmp_path):
    """Unset, the cache is one fixed, git-ignored directory of the checkout;
    set, ``JAX_COMPILATION_CACHE_DIR`` is used and JAX's config left alone."""
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        got = enable_compile_cache()
        assert os.path.samefile(os.path.dirname(got), ROOT)
        assert jax.config.jax_compilation_cache_dir == got
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert os.path.basename(got) + "/" in f.read().split()

        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_script_alone_fails(tmp_path):
    """Copied out of the repository, the script finds no program and fails
    without printing a result."""
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
