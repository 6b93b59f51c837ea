"""Render docs/API.md from the package's docstrings (the reference ships a
pdoc-generated API reference, docs/dampr/index.html; this is the equivalent
without a pdoc dependency).

Run: python docs/generate_api.py
"""

import importlib
import inspect
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # `python docs/generate_api.py` from any cwd
    sys.path.insert(0, _REPO_ROOT)

MODULES = [
    "dampr_tpu",
    "dampr_tpu.dampr",
    "dampr_tpu.base",
    "dampr_tpu.blocks",
    "dampr_tpu.dataset",
    "dampr_tpu.inputs",
    "dampr_tpu.graph",
    "dampr_tpu.plan",
    "dampr_tpu.plan.ir",
    "dampr_tpu.plan.passes",
    "dampr_tpu.plan.cost",
    "dampr_tpu.plan.model",
    "dampr_tpu.plan.explain",
    "dampr_tpu.plan.lower",
    "dampr_tpu.runner",
    "dampr_tpu.faults",
    "dampr_tpu.storage",
    "dampr_tpu.io",
    "dampr_tpu.io.codecs",
    "dampr_tpu.io.frames",
    "dampr_tpu.io.writer",
    "dampr_tpu.obs",
    "dampr_tpu.obs.trace",
    "dampr_tpu.obs.metrics",
    "dampr_tpu.obs.sampler",
    "dampr_tpu.obs.progress",
    "dampr_tpu.obs.promtext",
    "dampr_tpu.obs.flightrec",
    "dampr_tpu.obs.fleet",
    "dampr_tpu.obs.serve",
    "dampr_tpu.obs.export",
    "dampr_tpu.obs.profile",
    "dampr_tpu.obs.critpath",
    "dampr_tpu.obs.history",
    "dampr_tpu.obs.doctor",
    "dampr_tpu.obs.autotune",
    "dampr_tpu.obs.log",
    "dampr_tpu.obs.timeseries",
    "dampr_tpu.obs.sentry",
    "dampr_tpu.obs.top",
    "dampr_tpu.analyze",
    "dampr_tpu.analyze.props",
    "dampr_tpu.analyze.pickleprobe",
    "dampr_tpu.analyze.assoc",
    "dampr_tpu.analyze.jaxtrace",
    "dampr_tpu.analyze.validate",
    "dampr_tpu.analyze.lint",
    "dampr_tpu.resume",
    "dampr_tpu.serve",
    "dampr_tpu.serve.wire",
    "dampr_tpu.serve.scheduler",
    "dampr_tpu.serve.client",
    "dampr_tpu.serve.daemon",
    "dampr_tpu.serve.worker",
    "dampr_tpu.settings",
    "dampr_tpu.ops.hashing",
    "dampr_tpu.ops.segment",
    "dampr_tpu.ops.text",
    "dampr_tpu.ops.lower",
    "dampr_tpu.parallel",
    "dampr_tpu.parallel.mesh",
    "dampr_tpu.parallel.placement",
    "dampr_tpu.parallel.shuffle",
    "dampr_tpu.parallel.exchange",
    "dampr_tpu.parallel.replan",
    "dampr_tpu.parallel.mitigate",
    "dampr_tpu.parallel.sgd",
    "dampr_tpu.native",
    "dampr_tpu.utils",
    "dampr_tpu.utils.indexer",
    "dampr_tpu.utils.common",
]


import re as _re


def _sig(obj):
    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # strip live object addresses from default reprs so regenerated docs
    # are byte-stable
    return _re.sub(r" at 0x[0-9a-f]+", "", sig)


def _doc(obj, indent=""):
    d = inspect.getdoc(obj)
    if not d:
        return ""
    return "\n".join(indent + line for line in d.splitlines())


def render_module(name, out):
    mod = importlib.import_module(name)
    out.append("\n## `{}`\n".format(name))
    d = _doc(mod)
    if d:
        out.append(d + "\n")

    members = vars(mod)
    for attr, obj in sorted(members.items()):
        if attr.startswith("_"):
            continue
        if getattr(obj, "__module__", None) != name:
            continue
        if inspect.isclass(obj):
            out.append("\n### class `{}.{}`\n".format(name, attr))
            d = _doc(obj)
            if d:
                out.append(d + "\n")
            for mname, meth in sorted(vars(obj).items()):
                if mname.startswith("_") or not callable(meth):
                    continue
                fn = meth.__func__ if isinstance(
                    meth, (classmethod, staticmethod)) else meth
                out.append("- **`{}{}`**".format(mname, _sig(fn)))
                md = inspect.getdoc(fn)
                if md:
                    out.append("  - {}".format(md.splitlines()[0]))
        elif inspect.isfunction(obj):
            out.append("\n### `{}.{}{}`\n".format(name, attr, _sig(obj)))
            d = _doc(obj)
            if d:
                out.append(d + "\n")


def main():
    out = [
        "# dampr_tpu API reference",
        "",
        "*Generated from docstrings by `docs/generate_api.py` — regenerate "
        "after changing public surfaces.*",
    ]
    for name in MODULES:
        render_module(name, out)
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "API.md")
    with open(path, "w") as f:
        f.write("\n".join(out) + "\n")
    print("wrote", path, "({} lines)".format(sum(s.count("\n") + 1
                                                 for s in out)))


if __name__ == "__main__":
    main()
