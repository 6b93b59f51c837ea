#!/usr/bin/env python
"""Repo self-lint: the cross-cutting invariants that otherwise live
scattered across individual test files, consolidated into one
dependency-free command runnable locally and in CI (exit 0 = clean,
1 = violations, each printed with its location).

Checks:

1. **doctor playbook knobs exist** — every ``(setting, env, ...)``
   entry in :data:`dampr_tpu.obs.doctor._PLAYBOOK` names a real
   attribute of :mod:`dampr_tpu.settings` (a suggestion for a knob
   that's gone is worse than no suggestion).
2. **trace span kinds form a closed set** — every literal category
   passed to ``trace.span(...)`` / ``trace.instant(...)`` (or as the
   second argument of ``devtime.phase(...)``) in the
   package source is declared in ``docs/trace_schema.json``'s
   ``x-span-kinds``, and every declared kind still appears in the
   source (no dead schema entries).
3. **fault site catalog is documented** — every entry of
   :data:`dampr_tpu.faults.SITES` appears (backtick-quoted) in
   ``docs/robustness.md``.
4. **every env var is documented** — every ``DAMPR_TPU_*`` name used
   in the package source appears somewhere under ``docs/`` or in
   ``README.md``.
5. **structured event codes form a closed set** — every code passed to
   an ``obs.log`` emit site (``_obslog.debug/info/warn/error(...)`` or a
   direct ``stream.emit("level", "code", ...)``) is declared in
   :data:`dampr_tpu.obs.log.EVENT_CODES`, every declared code still has
   an emit site (no dead registry entries), and every code appears
   (backtick-quoted) in ``docs/observability.md``'s event table.

Usage::

    python tools/lint_repo.py [--root REPO_ROOT]
"""

import argparse
import json
import os
import re
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_DEFAULT_ROOT = os.path.abspath(os.path.join(_HERE, os.pardir))


def _package_sources(root):
    """{relpath: source} for every .py under dampr_tpu/."""
    out = {}
    pkg = os.path.join(root, "dampr_tpu")
    for dirpath, _dirs, files in os.walk(pkg):
        if "__pycache__" in dirpath:
            continue
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                with open(path) as f:
                    out[os.path.relpath(path, root)] = f.read()
    return out


def check_playbook_knobs(root, errors):
    from dampr_tpu import settings
    from dampr_tpu.obs import doctor

    for verdict, entries in sorted(doctor._PLAYBOOK.items()):
        for knob, env, _propose, _why in entries:
            if not hasattr(settings, knob):
                errors.append(
                    "playbook[{}]: suggests settings.{} which does not "
                    "exist".format(verdict, knob))
            if env and not env.startswith("DAMPR_TPU_"):
                errors.append(
                    "playbook[{}]: knob {} has malformed env {!r}".format(
                        verdict, knob, env))


_SPAN_RX = re.compile(
    r"""(?:(?:trace|_trace)\.(?:span|instant)\(|devtime\.phase\(\s*"""
    r"""['"][a-z_]+['"],)\s*['"]([a-z_0-9]+)['"]""")


def check_span_kinds(root, sources, errors):
    with open(os.path.join(root, "docs", "trace_schema.json")) as f:
        declared = set(json.load(f)["x-span-kinds"])
    used = {}
    for rel, src in sources.items():
        for m in _SPAN_RX.finditer(src):
            used.setdefault(m.group(1), rel)
    for kind, rel in sorted(used.items()):
        if kind not in declared:
            errors.append(
                "span kind {!r} (used in {}) not declared in "
                "docs/trace_schema.json x-span-kinds".format(kind, rel))
    blob = "\n".join(sources.values())
    for kind in sorted(declared):
        if '"{}"'.format(kind) not in blob \
                and "'{}'".format(kind) not in blob:
            errors.append(
                "x-span-kinds declares {!r} but no package source "
                "mentions it (dead schema entry?)".format(kind))


def check_fault_sites(root, errors):
    from dampr_tpu import faults

    with open(os.path.join(root, "docs", "robustness.md")) as f:
        doc = f.read()
    for site in faults.SITES:
        if "`{}`".format(site) not in doc:
            errors.append(
                "faults.SITES entry {!r} undocumented in "
                "docs/robustness.md".format(site))


_ENV_RX = re.compile(r"DAMPR_TPU_[A-Z0-9_]*[A-Z0-9]")


def check_env_docs(root, sources, errors):
    docs = []
    for fn in os.listdir(os.path.join(root, "docs")):
        if fn.endswith((".md", ".json")):
            with open(os.path.join(root, "docs", fn)) as f:
                docs.append(f.read())
    with open(os.path.join(root, "README.md")) as f:
        docs.append(f.read())
    blob = "\n".join(docs)
    used = {}
    for rel, src in sources.items():
        for m in _ENV_RX.finditer(src):
            used.setdefault(m.group(0), rel)
    for env, rel in sorted(used.items()):
        if env not in blob:
            errors.append(
                "env var {} (used in {}) undocumented under docs/ or "
                "README.md".format(env, rel))


_EVENT_RX = re.compile(
    r"""_obslog\.(?:debug|info|warn|error)\(\s*\n?\s*['"]([a-z0-9-]+)['"]""")
_EMIT_RX = re.compile(
    r"""\.emit\(\s*\n?\s*['"](?:debug|info|warn|error)['"],\s*"""
    r"""\n?\s*['"]([a-z0-9-]+)['"]""")


def check_event_codes(root, sources, errors):
    from dampr_tpu.obs import log as obslog

    declared = set(obslog.EVENT_CODES)
    used = {}
    for rel, src in sources.items():
        if rel.endswith(os.path.join("obs", "log.py")):
            continue  # the registry/module itself, not an emit site
        for rx in (_EVENT_RX, _EMIT_RX):
            for m in rx.finditer(src):
                used.setdefault(m.group(1), rel)
    for code, rel in sorted(used.items()):
        if code not in declared:
            errors.append(
                "event code {!r} (emitted in {}) not declared in "
                "obs.log.EVENT_CODES".format(code, rel))
    for code in sorted(declared - set(used)):
        errors.append(
            "EVENT_CODES declares {!r} but no package source emits it "
            "(dead registry entry?)".format(code))
    with open(os.path.join(root, "docs", "observability.md")) as f:
        doc = f.read()
    for code in sorted(declared):
        if "`{}`".format(code) not in doc:
            errors.append(
                "event code {!r} undocumented in docs/observability.md"
                .format(code))


def run(root):
    sys.path.insert(0, root)
    errors = []
    sources = _package_sources(root)
    check_playbook_knobs(root, errors)
    check_span_kinds(root, sources, errors)
    check_fault_sites(root, errors)
    check_env_docs(root, sources, errors)
    check_event_codes(root, sources, errors)
    return errors


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=_DEFAULT_ROOT)
    args = ap.parse_args(argv)
    errors = run(os.path.abspath(args.root))
    if errors:
        for e in errors:
            print("LINT:", e, file=sys.stderr)
        print("{} violation(s)".format(len(errors)), file=sys.stderr)
        return 1
    print("repo lint OK (playbook knobs, span kinds, fault sites, "
          "env docs, event codes)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
