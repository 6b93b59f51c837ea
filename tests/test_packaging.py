"""Packaging surface: pyproject metadata, console entry points, and the
bench driver hook all resolve (reference parity: setup.py:1-20)."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestPackaging:
    def test_pyproject_parses_and_lists_packages(self):
        tomllib = pytest.importorskip("tomllib")
        with open(os.path.join(ROOT, "pyproject.toml"), "rb") as f:
            meta = tomllib.load(f)
        assert meta["project"]["name"] == "dampr_tpu"
        pkgs = meta["tool"]["setuptools"]["packages"]
        for pkg in pkgs:
            path = os.path.join(ROOT, pkg.replace(".", os.sep))
            assert os.path.isdir(path), pkg
        scripts = meta["project"]["scripts"]
        assert set(scripts) == {"dampr-tpu-bench", "dampr-tpu-wc",
                                "dampr-tpu-tfidf", "dampr-tpu-stats",
                                "dampr-tpu-doctor", "dampr-tpu-lint",
                                "dampr-tpu-sentry", "dampr-tpu-top",
                                "dampr-tpu-history", "dampr-tpu-serve"}

    def test_console_entry_points_import(self):
        from dampr_tpu import cli

        for fn in (cli.bench, cli.wc, cli.tf_idf, cli.stats, cli.doctor,
                   cli.lint, cli.sentry, cli.top, cli.history_cli):
            assert callable(fn)

    def test_bench_driver_hook_is_thin_wrapper(self):
        import dampr_tpu.bench_tfidf as bt

        assert callable(bt.main)
        src = open(os.path.join(ROOT, "bench.py")).read()
        assert "bench_tfidf" in src  # driver hook delegates to the package

    def test_native_source_ships_with_package(self):
        assert os.path.exists(os.path.join(
            ROOT, "dampr_tpu", "native", "tokenizer.cpp"))
