"""The package graph under ``cloud_tpu/`` points down.

Every ``cloud_tpu.*`` import of a package (module-level and in-function,
read with ``ast``; nothing is imported) is in that package's allowed set:
the architecture's arrows, written once below.  The library is a stack,
each layer free to use every layer under it; the three entry packages sit
on top of it and nothing in the stack imports them.  The upward edges that
exist today are named exceptions, each with its ROADMAP debt (D17): the
list may only shrink, and an exception whose import has gone fails too.
"""

import ast
import os

import pytest

PACKAGE_ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), "cloud_tpu")

#: The library, bottom to top.  Packages of one layer may use each other.
STACK = (
    ("utils", "monitoring"),
    ("ops", "parallel"),
    ("models",),
    ("training",),
    ("serving",),
    ("fleet",),
)
BOTTOM = set(STACK[0])

ALLOWED = {}
_below = set()
for _layer in STACK:
    for _package in _layer:
        ALLOWED[_package] = (_below | set(_layer)) - {_package}
    _below |= set(_layer)
#: The entry packages plan and submit jobs; what each may reach is named.
ALLOWED["core"] = BOTTOM | {"parallel"}
ALLOWED["tuner"] = BOTTOM | {"core"}
ALLOWED["cloud_fit"] = BOTTOM | {"core", "parallel", "training"}

#: (file under cloud_tpu/, imported package): why it is still there.
UPWARD_TODAY = {
    ("models/export.py", "training"):
        "D17: save/load_pretrained go through training.checkpoint",
    ("parallel/selfcheck.py", "models"):
        "D17: the multi-chip self-check builds a model",
    ("parallel/selfcheck.py", "training"):
        "D17: ... and takes train steps with it",
    ("parallel/planner.py", "core"):
        "D17: the planner's signature names core.machine_config's type",
    ("utils/local_rig.py", "core"):
        "D17: the local rig runs core.deploy's start-up script",
    ("core/bootstrap.py", "training"):
        "D17: the job's entry point arms preemption and the compile cache",
    ("core/deploy.py", "fleet"):
        "D17: deploy validates replica roles through fleet.disagg",
}

PACKAGES = sorted(ALLOWED)


def _imports(package):
    """{(relative file, imported cloud_tpu package)} of one package."""
    found = set()
    for directory, _, names in os.walk(os.path.join(PACKAGE_ROOT, package)):
        for name in names:
            if not name.endswith(".py"):
                continue
            path = os.path.join(directory, name)
            with open(path, encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=path)
            modules = []
            for node in ast.walk(tree):
                if isinstance(node, ast.Import):
                    modules += [alias.name for alias in node.names]
                elif isinstance(node, ast.ImportFrom) and not node.level:
                    if node.module == "cloud_tpu":
                        modules += [f"cloud_tpu.{alias.name}"
                                    for alias in node.names]
                    else:
                        modules.append(node.module)
            relative = os.path.relpath(path, PACKAGE_ROOT).replace(
                os.sep, "/")
            for module in modules:
                parts = module.split(".")
                if parts[0] == "cloud_tpu" and len(parts) > 1:
                    found.add((relative, parts[1]))
    return found


def test_every_package_has_its_arrows():
    on_disk = sorted(
        name for name in os.listdir(PACKAGE_ROOT)
        if os.path.isdir(os.path.join(PACKAGE_ROOT, name))
        and not name.startswith("__"))
    assert on_disk == PACKAGES


@pytest.mark.parametrize("package", PACKAGES)
def test_package_imports_point_down(package):
    found = _imports(package)
    allowed = ALLOWED[package] | {package, "version"}
    upward = {edge for edge in found if edge[1] not in allowed}
    excused = {edge for edge in UPWARD_TODAY if edge[0].startswith(
        package + "/")}
    assert upward - excused == set(), (
        f"{package} may import {sorted(ALLOWED[package])} only")
    assert excused - upward == set(), (
        "an excused upward import has gone: take it off UPWARD_TODAY")
