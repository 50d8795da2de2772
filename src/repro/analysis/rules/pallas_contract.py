"""pallas-contract: kernel invocation invariants.

Three checks, each tied to a bug class this repo has actually hit or
designed around:

* **store indexing** — a traced scalar used directly as a store index
  (``o_ref[t] = v``) silently lowers to the wrong op on some backends
  (the PR 2 ``pl.store`` integer-indexing bug); dynamic store positions
  must go through ``pl.dslice``/``pl.ds``.  Loads are exempt: only the
  store path miscompiled.
* **grid/BlockSpec agreement** — every ``BlockSpec`` index_map must take
  exactly one argument per grid axis (default-valued extras are allowed,
  the ``flash_attention`` closure idiom).  A mismatch is a runtime error
  only on the first *compiled* run, which CPU-interpret CI never takes.
* **interpret plumbing** — every ``pl.pallas_call`` must thread an
  ``interpret=`` flag from a parameter; omitting it (or hard-coding a
  bool) strands the kernel on one backend, out of reach of the platform
  choice in ``repro.kernels.ops`` (interpret on CPU, native on TPU).
"""
from __future__ import annotations

import ast

from ..findings import Finding
from ..walker import Project

RULE = "pallas-contract"

_DSLICE_NAMES = {"dslice", "ds"}


def _index_elements(sl: ast.expr) -> list[ast.expr]:
    if isinstance(sl, ast.Tuple):
        return list(sl.elts)
    return [sl]


def _store_index_ok(elt: ast.expr) -> bool:
    if isinstance(elt, ast.Constant):  # literal int, Ellipsis, None
        return True
    if isinstance(elt, ast.Slice):
        return True
    if isinstance(elt, ast.UnaryOp) and isinstance(elt.operand, ast.Constant):
        return True
    if isinstance(elt, ast.Call):
        f = elt.func
        name = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else ""
        )
        return name in _DSLICE_NAMES
    return False


def check(project: Project) -> list[Finding]:
    findings: list[Finding] = []

    # -- store indexing inside kernels ------------------------------------
    for kern in sorted(project.kernels, key=lambda f: (f.path, f.qualname)):
        for node in ast.walk(kern.node):
            targets: list[ast.expr] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AugAssign):
                targets = [node.target]
            for tgt in targets:
                if not (
                    isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id.endswith("_ref")
                ):
                    continue
                for elt in _index_elements(tgt.slice):
                    if not _store_index_ok(elt):
                        findings.append(
                            Finding(
                                rule=RULE,
                                path=kern.path,
                                line=tgt.lineno,
                                symbol=kern.qualname,
                                message=f"store into `{tgt.value.id}` "
                                "indexes with a traced scalar: wrap "
                                "dynamic store positions in "
                                "pl.dslice(i, 1) (PR 2 store bug class)",
                            )
                        )
                        break

    # -- pallas_call site checks ------------------------------------------
    for site in project.pallas_sites:
        call = site.call.node
        where = site.call
        sym = where.enclosing.qualname if where.enclosing else "<module>"

        kwargs = {kw.arg: kw.value for kw in call.keywords if kw.arg}
        interp = kwargs.get("interpret")
        if interp is None:
            findings.append(
                Finding(
                    rule=RULE,
                    path=where.path,
                    line=call.lineno,
                    symbol=sym,
                    message="pallas_call without interpret= plumbing: "
                    "thread the interpret flag from the wrapper/module "
                    "switch so CPU CI and compiled runs share one path",
                )
            )
        elif isinstance(interp, ast.Constant) and isinstance(
            interp.value, bool
        ):
            findings.append(
                Finding(
                    rule=RULE,
                    path=where.path,
                    line=interp.lineno,
                    symbol=sym,
                    message=f"pallas_call hard-codes interpret="
                    f"{interp.value}: the platform choice in "
                    "repro.kernels.ops cannot reach this kernel",
                )
            )

        grid = kwargs.get("grid")
        grid_len = None
        if isinstance(grid, ast.Tuple):
            grid_len = len(grid.elts)
        elif isinstance(grid, ast.Constant) and isinstance(grid.value, int):
            grid_len = 1
        if grid_len is None:
            continue
        for spec_kw in ("in_specs", "out_specs", "out_spec"):
            spec = kwargs.get(spec_kw)
            if spec is None:
                continue
            for sub in ast.walk(spec):
                if not isinstance(sub, ast.Call):
                    continue
                fname = (
                    sub.func.attr
                    if isinstance(sub.func, ast.Attribute)
                    else (sub.func.id if isinstance(sub.func, ast.Name) else "")
                )
                if fname != "BlockSpec":
                    continue
                for arg in list(sub.args) + [k.value for k in sub.keywords]:
                    if isinstance(arg, ast.Lambda):
                        a = arg.args
                        required = len(a.posonlyargs) + len(a.args) - len(
                            a.defaults
                        )
                        if required != grid_len:
                            findings.append(
                                Finding(
                                    rule=RULE,
                                    path=where.path,
                                    line=arg.lineno,
                                    symbol=sym,
                                    message=f"BlockSpec index_map takes "
                                    f"{required} grid args but the grid "
                                    f"has {grid_len} axes: the mismatch "
                                    "only errors on the first compiled "
                                    "(non-interpret) run",
                                )
                            )
    return findings
