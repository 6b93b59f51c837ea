"""``chip_smoke.py``: its contract off the chip, and its pipelines at a
tiny size on the CPU with lowering forced (the chip run's rehearsal)."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run_alone(tmp_path, pythonpath):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), str(tmp_path))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, "chip_smoke.py", "--mb", "1", "--records", "1000"],
        cwd=str(tmp_path), env=env, capture_output=True, text=True,
        timeout=300)


@pytest.mark.parametrize("with_repo", [True, False])
def test_fails_without_a_tpu_or_the_repo(tmp_path, with_repo):
    """No TPU (repo importable) or no repo beside the script: a non-zero
    exit and never the ok line."""
    proc = _run_alone(tmp_path, ROOT if with_repo else None)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if with_repo:
        assert "needs 1 TPU device(s)" in proc.stderr


def test_pipelines_exact_on_cpu(tmp_path, monkeypatch):
    """All three pipelines, cold and warm, exact against the oracles with
    every device check in force (lowering forced on the CPU backend)."""
    import jax

    from dampr_tpu import settings

    monkeypatch.setattr(chip_smoke, "SMOKE_DIR", str(tmp_path))
    monkeypatch.setattr(settings, "lower", "on")
    monkeypatch.setattr(settings, "hbm_budget", 1 << 30)
    monkeypatch.setattr(settings, "scratch_root", settings.scratch_root)
    monkeypatch.setenv("DAMPR_TPU_SCRATCH", settings.scratch_root)
    args = chip_smoke.parse_args(["--mb", "2", "--records", "60000"])
    corpus = chip_smoke.Corpus(args.seed, args.mb, args.records,
                               args.join_records)
    corpus.start()
    assert chip_smoke.smoke(args, corpus, jax.devices()[0]) == []
    # The oracle is cached beside the corpus, keyed by seed and size.
    again = chip_smoke.Corpus(args.seed, args.mb, args.records,
                              args.join_records)
    again.start()
    assert again._pool is None  # no regeneration
    assert again.wait()["words"] == corpus.oracle["words"]


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_compile_cache_placement(tmp_path, env_dir):
    """The deployment's JAX_COMPILATION_CACHE_DIR wins; unset, the cache
    sits at the fixed ``<checkout>/.jax_cache`` — whether jax or the
    package is imported first."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = os.path.join(ROOT, ".jax_cache")
    if env_dir:
        want = env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / env_dir)
    for first, second in (("dampr_tpu", "jax"), ("jax", "dampr_tpu")):
        out = subprocess.run(
            [sys.executable, "-c",
             "import {}, {}, jax; print(jax.config.jax_compilation_cache_dir)"
             .format(first, second)],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
        assert out.stdout.strip() == want, out.stderr


def test_mesh_path_matches_host_and_oracle(tmp_path, monkeypatch):
    """The ``--chips 4`` phases on the rig's virtual CPU mesh: word count,
    join and group_by with the mesh fold/exchange in force, then off."""
    import jax

    from dampr_tpu import settings

    monkeypatch.setattr(chip_smoke, "SMOKE_DIR", str(tmp_path))
    for name, value in (("lower", "on"), ("hbm_budget", 1 << 30),
                        ("mesh_fold", "auto"), ("mesh_exchange", "auto"),
                        ("scratch_root", settings.scratch_root)):
        monkeypatch.setattr(settings, name, value)
    monkeypatch.setenv("DAMPR_TPU_SCRATCH", settings.scratch_root)
    args = chip_smoke.parse_args(["--chips", "4", "--mb", "2", "--records",
                                  "1000", "--join-records", "20000"])
    corpus = chip_smoke.Corpus(args.seed, args.mb, args.records,
                               args.join_records)
    corpus.start()
    assert chip_smoke.smoke(args, corpus, jax.devices()[0]) == []
