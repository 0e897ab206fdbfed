"""chip_smoke.py: refuses to run without a TPU, and its phases hold on a
tiny graph here (Pallas in interpret mode, four virtual CPU devices for
the sharded phase)."""

import os
import shutil
import subprocess
import sys

import pytest

from repro.core import walk as walk_lib
from repro.graphs.synthetic import small_test_graph
from test_distributed import REPO, _run

sys.path.insert(0, REPO)
import chip_smoke  # noqa: E402

SMOKE_WALK = walk_lib.WalkConfig(
    n_steps=1024, n_walkers=128, chunk_steps=4, top_k=20, n_p=60, n_v=3
)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_tpu(tmp_path, where):
    """No TPU (or none of the repo beside the script): non-zero exit and
    no result line."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":
        script = shutil.copy(script, tmp_path)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, env=env,
        cwd=tmp_path, timeout=120,
    )
    assert out.returncode != 0
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("from_env", [True, False], ids=["env", "repo"])
def test_compile_cache_dir(tmp_path, from_env):
    """The entry points' cache is ``$JAX_COMPILATION_CACHE_DIR`` when set
    (left to JAX), else the fixed ``<repo>/.jax_cache``."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(REPO, ".jax_cache")
    if from_env:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cache")
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax\n"
         "from repro.launch.compile_cache import enable_compile_cache\n"
         "print(enable_compile_cache())\n"
         "print(jax.config.jax_compilation_cache_dir)\n"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=120,
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [want, want]


def test_one_chip_phase_agrees_with_oracle(monkeypatch):
    """Both gather modes and the ranked replica equal the xla oracle
    request for request (interpret mode lowers no Mosaic call, so the
    kernel count is stubbed)."""
    g = small_test_graph().graph
    monkeypatch.setattr(chip_smoke, "kernel_calls", lambda *a: 1)
    requests = chip_smoke.homefeed_requests(g, chip_smoke.N_REQUESTS, 0)
    chip_smoke.one_chip(g, SMOKE_WALK, requests)


def test_four_chip_phase_places_shards_and_agrees():
    res = _run(4, f"""
        import sys
        sys.path.insert(0, {REPO!r})
        import chip_smoke
        from repro.core import walk as W
        from repro.graphs.synthetic import small_test_graph

        g = small_test_graph().graph
        cfg = W.WalkConfig(n_steps=1024, n_walkers=128, chunk_steps=4,
                           top_k=20, n_p=60, n_v=3)
        chip_smoke.four_chips(
            g, cfg, chip_smoke.homefeed_requests(g, chip_smoke.BATCH, 0))
        print(json.dumps({{"ok": True}}))
    """)
    assert res["ok"]
