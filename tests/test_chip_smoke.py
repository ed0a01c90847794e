"""CPU rehearsal of ``chip_smoke.py``: its phases at ``reduced()`` size with
the Pallas kernels in interpret mode, and its refusal to run off a TPU.

The script's phases take a config and a ``KernelConfig``; the tests steer
them here, so the script itself carries no rehearsal option. The
four-chip phase runs in a child process on four host devices (the device
count locks at jax's first init), so this process keeps one device.
"""

import importlib.util
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.configs import get_config
from repro.models import KernelConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMALL = dict(slots=4, cap=64, prefill_len=16, new_tokens=4, steps=2)
# Prompts long enough that capacity factor 1.25 would drop tokens: the
# four-chip phase must make both paths dropless to compare them.
FOUR = dict(SMALL, cap=128, prefill_len=64)


def _load_script():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def smoke():
    return _load_script()


def test_main_refuses_without_tpu(smoke, capsys):
    rc = smoke.main([])
    out, err = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in out
    assert "needs a TPU" in err


def test_one_chip_phase_reduced_interpret(smoke, capsys):
    cfg = get_config(smoke.ARCH).reduced()
    assert smoke.one_chip_phase(cfg, KernelConfig(interpret=True), 0,
                                **SMALL)
    out = capsys.readouterr().out
    assert "4/4 complete" in out
    assert "kernel vs dense prefill" in out and "FAIL" not in out


def test_smoke_config_keeps_published_widths(smoke):
    full, cut = get_config(smoke.ARCH), smoke.smoke_config()
    assert cut.n_layers == smoke.N_LAYERS < full.n_layers
    for f in ("d_model", "n_heads", "n_kv_heads", "head_dim", "vocab",
              "moe", "dtype"):
        assert getattr(cut, f) == getattr(full, f), f


def test_compare_logits_flags_a_wrong_path(smoke):
    import numpy as np

    rng = np.random.default_rng(0)
    ref = [rng.standard_normal((6, 32)) for _ in range(3)]
    near = [r + 1e-3 * rng.standard_normal(r.shape) for r in ref]
    far = [rng.standard_normal(r.shape) for r in ref]
    assert all(c["ok"] and c["agree"] == 1.0
               for c in smoke.compare_logits(ref, near).values())
    assert not any(c["ok"] for c in smoke.compare_logits(ref, far).values())


def test_compile_cache_directory(monkeypatch, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` wins and nothing is set in code;
    unset, the cache goes to the fixed, git-ignored ``<checkout>/.jax_cache``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    from repro.launch.compile_cache import enable_compile_cache

    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == prev
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        compilation_cache.reset_cache()
    assert ".jax_cache/" in open(os.path.join(REPO, ".gitignore")).read()


def test_four_chip_phase_reduced_on_host_mesh():
    """The ``--four-chips`` phase on four CPU devices: Aurora rounds and
    ``all_to_all`` both match the one-device kernel path, and the experts
    split evenly over the devices."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    body = f"""
    import importlib.util, json
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", {os.path.join(REPO, "chip_smoke.py")!r})
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    from repro.configs import get_config
    from repro.models import KernelConfig
    cfg = get_config(smoke.ARCH).reduced()
    ok = smoke.four_chip_phase(cfg, KernelConfig(interpret=True), 0,
                               **{json.dumps(FOUR)})
    print("RESULT", json.dumps(ok))
    """
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    assert "RESULT true" in out.stdout, out.stdout
    # 4 reduced experts over 4 devices: one each.
    assert "device 3: 3..3" in out.stdout, out.stdout
    for impl in ("aurora", "ep"):
        assert f"{impl}: " in out.stdout
    assert out.stdout.count("4/4 requests complete") == 2, out.stdout
