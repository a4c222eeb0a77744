"""chip_smoke.py off the chip (PR 21): the rehearsal walks every phase
tiny with interpret-mode kernels and passes; without the argument the
script refuses before building anything; and the program's one compile
cache is where the environment says, else ``<checkout>/.jax_cache``.
What the chip itself decides is checked by running the script there."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    """A one-device CPU child with JAX's compilation cache live (the
    suite's own process runs with it off — see conftest.py)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for name in ("XLA_FLAGS", "JAX_ENABLE_COMPILATION_CACHE",
                 "JAX_COMPILATION_CACHE_DIR"):
        env.pop(name, None)
    env.update(extra)
    return env


def test_rehearsal_walks_every_phase(tmp_path):
    cache = str(tmp_path / "jaxcache")
    proc = subprocess.run(
        [sys.executable, SMOKE, "--rehearse-cpu"], cwd=REPO,
        env=_env(JAX_COMPILATION_CACHE_DIR=cache), capture_output=True,
        text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert lines[0].startswith("device: platform: cpu ")
    phases = [json.loads(ln)["phase"] for ln in lines
              if ln.startswith('{"phase"')]
    assert phases == ["train", "train", "kernels", "serve"]
    assert "multichip: not run, 1 device" in lines
    # the environment named the cache: the program set no other
    assert f"compile cache: {cache} (JAX_COMPILATION_CACHE_DIR)" \
        in lines[1]


def test_refuses_without_a_chip():
    proc = subprocess.run([sys.executable, SMOKE], cwd=REPO, env=_env(),
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    # the device line and nothing else: no phase ran, no result printed
    out = proc.stdout.strip().splitlines()
    assert len(out) == 1 and out[0].startswith("device: platform: cpu ")
    assert "no TPU" in proc.stderr


def test_place_compile_cache_defaults_to_the_checkout():
    """Importing the package (conftest did) placed the cache — unless
    the environment had named a directory, which then stands."""
    import jax

    import paddle_tpu  # noqa: F401

    assert jax.config.jax_compilation_cache_dir == os.environ.get(
        "JAX_COMPILATION_CACHE_DIR", os.path.join(REPO, ".jax_cache"))
