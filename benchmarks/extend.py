"""One resolver for what a deployment brings as files.

A later PR may add files under `benchmarks/` and may edit none that is
there. So whatever belongs to one cell is found by the name its data
files give: a name a built-in table has is the built-in; any other name
is the module `benchmarks/<directory>/<name>.py` and its one agreed
attribute (README.md, "Add a cell"):

  kind        directory   attribute   the built-in table
  check       checks/     check       check.CHECKS
  law         laws/       Stream      gen.LAWS
  role        roles/      Role        client.ROLES
  reduction   reduce/     reduce      reductions.REDUCTIONS
  kernel      kernels/    least       roofline.KERNELS

A file can add a name, never replace one: a file whose name a built-in
has is an error as soon as the directories are looked at (`use`), and a
name that neither a table nor a file provides is an error that names
the file looked for. Both are `RunFailed`: the harness raises them
before a manager child exists, and the run exits 1 with no result line.
"""

from __future__ import annotations

import importlib
import os
import sys
from types import ModuleType
from typing import Dict, Optional, Tuple

#: kind → (directory, agreed attribute, module of the built-in table, table)
KINDS: Dict[str, Tuple[str, str, str, str]] = {
    "check": ("checks", "check", "check", "CHECKS"),
    "law": ("laws", "Stream", "gen", "LAWS"),
    "role": ("roles", "Role", "client", "ROLES"),
    "reduction": ("reduce", "reduce", "reductions", "REDUCTIONS"),
    "kernel": ("kernels", "least", "roofline", "KERNELS"),
}
#: also looked for in an overlay: where a check file keeps its reference
DIRECTORIES = [k[0] for k in KINDS.values()] + ["references"]


class RunFailed(Exception):
    """The run cannot give a result (no chip, a child died, a phase
    timed out, a name nothing provides): exit non-zero, print no
    result line."""


def builtins(kind: str) -> Dict[str, object]:
    _, _, module, table = KINDS[kind]
    return getattr(importlib.import_module(f"{__package__}.{module}"), table)


def _package(directory: str) -> ModuleType:
    return importlib.import_module(f"{__package__}.{directory}")


def use(base: str) -> None:
    """Look for extension files under `base` as well (the benchmark's
    own directory always is; selftest.py and the tests hand in an
    overlay), and refuse a file that shadows a built-in name."""
    for directory in DIRECTORIES:
        pkg, path = _package(directory), os.path.join(base, directory)
        if os.path.isdir(path) and path not in pkg.__path__:
            pkg.__path__.append(path)
    importlib.invalidate_caches()      # files written since the last look
    for kind, (directory, _, module, table) in KINDS.items():
        for path in _package(directory).__path__:
            for name in builtins(kind):
                shadow = os.path.join(path, name + ".py")
                if os.path.exists(shadow):
                    raise RunFailed(
                        f"{shadow} has the name of a built-in {kind} "
                        f"({module}.py {table}): a file adds a name, it "
                        f"never replaces one")


def forget(base: str) -> None:
    """Undo `use(base)` for an overlay: its directories are no longer
    looked in and what was imported from them is dropped, so that a
    file of the same name can be read anew."""
    for directory in DIRECTORIES:
        pkg, path = _package(directory), os.path.join(base, directory)
        if path in pkg.__path__[1:]:
            pkg.__path__.remove(path)
        for full, mod in list(sys.modules.items()):
            if full.startswith(pkg.__name__ + ".") and \
                    os.path.dirname(getattr(mod, "__file__", "") or "") == path:
                del sys.modules[full]
                delattr(pkg, full.rsplit(".", 1)[1])


def module(kind: str, name: str) -> Optional[ModuleType]:
    """The file that brings `name`; None for a built-in."""
    if name in builtins(kind):
        return None
    directory, attr = KINDS[kind][:2]
    if not name.isidentifier():
        raise RunFailed(f"no {kind} {name!r}: a name a file can bring is "
                        f"a Python identifier ({directory}/<name>.py)")
    full = f"{__package__}.{directory}.{name}"
    try:
        mod = importlib.import_module(full)
    except ModuleNotFoundError as e:
        if e.name != full:               # the file is there, its import is not
            raise
        raise RunFailed(
            f"no {kind} {name!r}: not one of {sorted(builtins(kind))} and "
            f"no file benchmarks/{directory}/{name}.py") from None
    if not hasattr(mod, attr):
        raise RunFailed(f"{mod.__file__} defines no `{attr}`, the agreed "
                        f"attribute of a {kind} file")
    return mod


def resolve(kind: str, name: str):
    """The built-in of that name, else the agreed attribute of its file."""
    mod = module(kind, name)
    return builtins(kind)[name] if mod is None else getattr(mod, KINDS[kind][1])
