"""zlint rule: the environment does not route the step (``env-routing``).

Which kernel, tier or rewrite a layer takes is decided by the code from
what it can observe — the layer list, the operands' shapes and dtypes,
the platform — in ``znicz_tpu/ops/`` and ``znicz_tpu/parallel/``.  A
routing that a user sets through the environment is a path no benchmark
cell runs and every test has to name through ``monkeypatch.setenv``
(ROADMAP D2 was five such variables).  So under those two packages any
touch of ``os.environ`` / ``os.getenv`` is a finding, except a read of
one of the switches that stay:

* ``ZNICZ_TPU_PALLAS_INTERPRET`` — the Pallas interpreter off-TPU, how
  the tests run the kernels' logic;
* ``ZNICZ_TPU_NO_PALLAS`` — the operator's fallback to the XLA tier,
  which is also the tests' reference.

A deployment setting read there (an address, a path) is suppressed
inline with its reason, like any other rule.
"""

from __future__ import annotations

import ast

from .core import Rule, dotted

#: packages whose code picks the step's routes
SCOPE = ("znicz_tpu/ops/", "znicz_tpu/parallel/")

#: the switches that stay (module docstring)
ALLOWED = frozenset({"ZNICZ_TPU_PALLAS_INTERPRET", "ZNICZ_TPU_NO_PALLAS"})


def _const_str(node) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


class EnvRoutingRule(Rule):
    id = "env-routing"
    severity = "error"
    doc = ("os.environ touched under znicz_tpu/ops/ or "
           "znicz_tpu/parallel/: routing is decided from layer list, "
           "shapes and platform, not set by the user")

    def check(self, module) -> list:
        if not module.path.startswith(SCOPE):
            return []
        # local names of os.environ / os.getenv (``from os import ...``)
        environ, getenv = set(), set()
        for node in ast.walk(module.tree):
            if isinstance(node, ast.ImportFrom) and node.module == "os":
                for alias in node.names:
                    if alias.name == "environ":
                        environ.add(alias.asname or alias.name)
                    elif alias.name == "getenv":
                        getenv.add(alias.asname or alias.name)
        parents = {child: node for node in ast.walk(module.tree)
                   for child in ast.iter_child_nodes(node)}

        def names(node, attr, local) -> bool:
            """Whether ``node`` is ``os.<attr>`` or a local name of it."""
            path = dotted(node)
            return path is not None and (
                path[-2:] == ("os", attr)
                or (len(path) == 1 and path[0] in local))

        findings = []

        def judge(node, name):
            if name in ALLOWED:
                return
            what = (f"reads {name!r} from the environment" if name
                    else "touches os.environ")
            findings.append(module.finding(
                self, node,
                f"{what}: a route of the step is picked from what the "
                f"code observes (layer list, shapes, platform), not "
                f"set by the user; only {', '.join(sorted(ALLOWED))} "
                f"are read here"))

        for node in ast.walk(module.tree):
            if isinstance(node, ast.Call) \
                    and names(node.func, "getenv", getenv):
                judge(node, _const_str(node.args[0]) if node.args
                      else None)
            elif names(node, "environ", environ):
                parent = parents.get(node)
                name = None
                if isinstance(parent, ast.Attribute) \
                        and parent.attr == "get":
                    call = parents.get(parent)
                    if isinstance(call, ast.Call) and call.args:
                        name = _const_str(call.args[0])
                elif isinstance(parent, ast.Subscript) \
                        and isinstance(parent.ctx, ast.Load):
                    name = _const_str(parent.slice)
                elif isinstance(parent, ast.Compare) \
                        and parent.comparators == [node]:
                    name = _const_str(parent.left)
                judge(node, name)
        return findings
