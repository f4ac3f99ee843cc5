"""Static hot-path observability discipline for the new coll engines,
the wire transport, the cross-process tracing layer, and the
continuous sampler itself.

``coll/pipeline.py``, ``coll/fusion.py``, ``runtime/wire.py``,
``coll/hier.py``, ``osc/wire_win.py``, ``p2p/pml.py``,
``btl/components.py``, and ``obs/sampler.py`` sit on hot paths (the
wire router is EVERY cross-process byte; the sampler's disabled state
must cost literally nothing); PR 1's contract is that observability
costs ONE attribute check (``_obs.enabled`` / ``_watchdog.enabled``)
when off.
This test enforces it statically, without importing jax: every emit
site (journal ``record``, skew ``begin/body/end``, stall-watchdog
``arm``/``disarm``, per-call pvar registry lookups) must be gated on
an ``enabled`` flag, and every pvar bump (``.add``/``.observe``) must
target a MODULE-LEVEL pre-registered pvar (the zero-cost-counter
class the driver already uses) or itself be gated.

Gating shapes recognized:

- ``if _obs.enabled: <emit>``   (including ``and``-compounds)
- ``if not _obs.enabled: return`` followed by the emit (early-return)
- ``if tok is not None: _watchdog.disarm(tok)`` — disarm of a token
  that only exists under an enabled gate
"""

import ast
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKED = ("ompi_release_tpu/coll/pipeline.py",
           "ompi_release_tpu/coll/fusion.py",
           "ompi_release_tpu/runtime/wire.py",
           "ompi_release_tpu/coll/hier.py",
           "ompi_release_tpu/coll/hier_schedules.py",
           "ompi_release_tpu/osc/wire_win.py",
           "ompi_release_tpu/p2p/pml.py",
           "ompi_release_tpu/btl/components.py",
           "ompi_release_tpu/obs/sampler.py",
           "ompi_release_tpu/runtime/progress.py",
           "ompi_release_tpu/coll/nbc.py",
           "ompi_release_tpu/ft/ulfm.py",
           "ompi_release_tpu/parallel/elastic.py",
           "ompi_release_tpu/obs/sentinel.py",
           "ompi_release_tpu/parallel/tree.py",
           "ompi_release_tpu/coll/plan.py",
           "ompi_release_tpu/coll/topo_schedules.py",
           "ompi_release_tpu/tuning/db.py",
           "ompi_release_tpu/tuning/retune.py",
           "ompi_release_tpu/service/qos.py",
           "ompi_release_tpu/service/tenant.py",
           "ompi_release_tpu/obs/ledger.py",
           "ompi_release_tpu/obs/nativeev.py",
           "ompi_release_tpu/btl/nativewire.py",
           "ompi_release_tpu/osc/plan.py",
           "ompi_release_tpu/oshmem/shmem.py",
           "ompi_release_tpu/coll/native_exec.py")

#: attribute calls that ARE emit sites when ungated
EMIT_ATTRS = {"record", "begin", "body", "end", "arm"}
#: per-call pvar registry lookups (allocate/lock per call — never on
#: an ungated hot path; module scope is where registration belongs)
REGISTRY_ATTRS = {"counter", "aggregate", "histogram", "timer",
                  "highwatermark"}
#: bumps allowed ungated ONLY on module-level pvars
BUMP_ATTRS = {"add", "observe"}
#: receiver-name tokens that mark an emit-capable object
OBS_BASES = ("obs", "skew", "journal", "JOURNAL", "watchdog")


def _mentions_enabled(node) -> bool:
    return any(
        (isinstance(n, ast.Attribute) and n.attr == "enabled")
        or (isinstance(n, ast.Name) and n.id == "enabled")
        for n in ast.walk(node)
    )


def _terminates(stmts) -> bool:
    return bool(stmts) and isinstance(
        stmts[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


def _module_pvars(tree) -> set:
    """Names bound at module level to pvar registrations."""
    out = set()
    for stmt in tree.body:
        if isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call):
            f = stmt.value.func
            attr = f.attr if isinstance(f, ast.Attribute) else \
                (f.id if isinstance(f, ast.Name) else None)
            if attr in REGISTRY_ATTRS:
                out.update(t.id for t in stmt.targets
                           if isinstance(t, ast.Name))
    return out


def _is_registry_call(value) -> bool:
    return (isinstance(value, ast.Call)
            and isinstance(value.func, ast.Attribute)
            and value.func.attr in REGISTRY_ATTRS)


def _assign_targets(node):
    if isinstance(node, ast.Assign):
        return node.targets, node.value
    if isinstance(node, ast.AnnAssign) and node.value is not None:
        return [node.target], node.value
    return [], None


def _import_names(node) -> set:
    """Names bound by an import statement. An imported pvar is a
    module-level registration living in ANOTHER module — bumping it is
    the allowed zero-cost-counter pattern, not per-call allocation."""
    if isinstance(node, (ast.Import, ast.ImportFrom)):
        return {(a.asname or a.name).split(".")[0] for a in node.names}
    return set()


def _module_containers(tree) -> set:
    """Module-level names visibly bound to something OTHER than a pvar
    registration (``_services = weakref.WeakSet()``, imports): their
    ``.add`` calls are container ops or cross-module pvar references,
    exempt from the bump check."""
    out = set()
    for stmt in tree.body:
        targets, value = _assign_targets(stmt)
        if value is not None and not _is_registry_call(value):
            out.update(t.id for t in targets if isinstance(t, ast.Name))
        out |= _import_names(stmt)
    return out


def _bound_containers(func_node) -> set:
    """Names visibly bound inside the function to anything that is NOT
    a pvar-registry call — locals, loop vars, with-targets,
    comprehension vars. Their ``.add``/``.observe`` are container ops.
    Names with no such binding — including bare parameters — stay
    checkable, so a pvar handle smuggled in as an argument and bumped
    ungated is still flagged (the one-attr-check-off contract)."""
    out = set()

    def names(t):
        return [x.id for x in ast.walk(t) if isinstance(x, ast.Name)]

    for n in ast.walk(func_node):
        out |= _import_names(n)
        targets, value = _assign_targets(n)
        if value is not None and not _is_registry_call(value):
            for t in targets:
                out.update(names(t))
        elif isinstance(n, (ast.For, ast.AsyncFor)):
            out.update(names(n.target))
        elif isinstance(n, (ast.With, ast.AsyncWith)):
            for item in n.items:
                if item.optional_vars is not None:
                    out.update(names(item.optional_vars))
        elif isinstance(n, ast.comprehension):
            out.update(names(n.target))
    return out


def _check_calls(node, gated, pvars, violations, path, exempt=()):
    """Check every Call in an expression subtree (no statements here)."""
    for n in ast.walk(node):
        if not isinstance(n, ast.Call):
            continue
        f = n.func
        if not isinstance(f, ast.Attribute):
            continue
        where = f"{path}:{n.lineno}"
        if f.attr in EMIT_ATTRS and not gated:
            # record/begin/body/end/arm on obs-ish receivers; skip
            # unrelated receivers (e.g. dict methods named the same)
            base = f.value
            base_name = (base.id if isinstance(base, ast.Name) else
                         base.attr if isinstance(base, ast.Attribute)
                         else "")
            if any(t in base_name for t in OBS_BASES):
                violations.append(
                    f"{where}: ungated emit {base_name}.{f.attr}()")
        if f.attr in REGISTRY_ATTRS and not gated:
            base = f.value
            if isinstance(base, ast.Name) and base.id in ("pvar",
                                                          "_pvar"):
                violations.append(
                    f"{where}: per-call pvar registry lookup "
                    f"{base.id}.{f.attr}() on the hot path")
        if f.attr in BUMP_ATTRS and not gated:
            base = f.value
            if isinstance(base, ast.Name) and base.id not in pvars \
                    and base.id not in exempt:
                violations.append(
                    f"{where}: {base.id}.{f.attr}() bumps a "
                    f"non-module-level pvar ungated")


def _scan_stmts(stmts, gated, pvars, violations, path, exempt=()):
    for stmt in stmts:
        if isinstance(stmt, ast.If) and _mentions_enabled(stmt.test):
            neg = (isinstance(stmt.test, ast.UnaryOp)
                   and isinstance(stmt.test.op, ast.Not))
            _check_calls(stmt.test, gated, pvars, violations, path,
                         exempt)
            if neg:
                _scan_stmts(stmt.body, gated, pvars, violations, path,
                            exempt)
                _scan_stmts(stmt.orelse, True, pvars, violations, path,
                            exempt)
                if _terminates(stmt.body):
                    gated = True  # `if not enabled: return` early-out
            else:
                _scan_stmts(stmt.body, True, pvars, violations, path,
                            exempt)
                _scan_stmts(stmt.orelse, gated, pvars, violations, path,
                            exempt)
            continue
        # other statements: recurse into child statement lists with the
        # same gating, check the non-statement (expression) children
        for field, value in ast.iter_fields(stmt):
            if isinstance(value, list) and value \
                    and isinstance(value[0], ast.stmt):
                _scan_stmts(value, gated, pvars, violations, path,
                            exempt)
            elif isinstance(value, list):
                for v in value:
                    if isinstance(v, ast.excepthandler):
                        _scan_stmts(v.body, gated, pvars, violations,
                                    path, exempt)
                    elif isinstance(v, ast.AST):
                        _check_calls(v, gated, pvars, violations, path,
                                     exempt)
            elif isinstance(value, ast.AST):
                _check_calls(value, gated, pvars, violations, path,
                             exempt)


def _scan_file(rel):
    path = os.path.join(REPO, rel)
    tree = ast.parse(open(path).read(), filename=rel)
    pvars = _module_pvars(tree)
    assert pvars, f"{rel}: expected module-level pvar registrations"
    mod_containers = _module_containers(tree)
    violations = []
    # scan only function bodies (module scope runs once at import)
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            _scan_stmts(node.body, False, pvars, violations, rel,
                        mod_containers | _bound_containers(node))
    return violations


def test_hot_path_emit_sites_are_gated():
    checked_any_gate = 0
    for rel in CHECKED:
        violations = _scan_file(rel)
        assert not violations, "\n".join(violations)
        # non-vacuous: each file must actually contain a gated emit,
        # its own or (oshmem/shmem.py since ISSUE 36) the journal pair
        # of a span, which obs/spans.py writes under the same gate
        src = open(os.path.join(REPO, rel)).read()
        assert ("_obs.enabled" in src and "_obs.record" in src
                or "_obs.span(" in src and "journal=(" in src), (
            f"{rel}: expected at least one _obs.enabled-gated "
            f"_obs.record emit site")
        checked_any_gate += 1
    assert checked_any_gate == len(CHECKED)


# -- library spans (obs.span): few exact names, one helper --------------------

SPAN_FILES = CHECKED + ("ompi_release_tpu/comm/communicator.py",
                        "ompi_release_tpu/coll/driver.py")
SPANS_MODULE = "ompi_release_tpu/obs/spans.py"


def _span_name_violations(tree, rel):
    """``span(...)`` calls whose name is not a string literal or a
    constant (``NAME`` / ``module.NAME``, all capitals): an f-string or
    a computed name would multiply the names a metric's exact pattern
    has to match."""
    out = []
    for n in ast.walk(tree):
        if not (isinstance(n, ast.Call) and (
                (isinstance(n.func, ast.Attribute) and n.func.attr == "span")
                or (isinstance(n.func, ast.Name) and n.func.id == "span"))):
            continue
        arg = n.args[0] if n.args else None
        const = (isinstance(arg, ast.Constant) and isinstance(arg.value, str))
        ident = (arg.attr if isinstance(arg, ast.Attribute)
                 and isinstance(arg.value, ast.Name)
                 else arg.id if isinstance(arg, ast.Name) else "")
        if not (const or (ident and ident.isupper())):
            out.append(f"{rel}:{n.lineno}: span() name is not a constant")
    return out


@pytest.mark.parametrize("rel", SPAN_FILES)
def test_span_names_are_constants(rel):
    tree = ast.parse(open(os.path.join(REPO, rel)).read(), filename=rel)
    assert not _span_name_violations(tree, rel)


def test_span_name_checker_catches_violations():
    bad = ("def f(op):\n"
           "    with _obs.span(f'ompi.coll.{op}'):\n"       # VIOLATION
           "        pass\n"
           "    with _obs.span('ompi.' + op, bytes=1):\n"   # VIOLATION
           "        pass\n"
           "    with span(name):\n"                         # VIOLATION
           "        pass\n"
           "    with _obs.span(_spans.COLL_CALL, op=op):\n"
           "        pass\n"
           "    with _obs.span('ompi.coll.call'):\n"
           "        pass\n"
           "    with span(HIER_D2H):\n"
           "        pass\n")
    assert len(_span_name_violations(ast.parse(bad), "bad.py")) == 3


def test_span_sites_exist_and_only_the_helper_annotates():
    """Every layer boundary of the table has its site, never inside an
    ``if _obs.enabled`` block (they fire with the journal off: the
    profiler session is the gate), and ``TraceAnnotation`` appears in
    the helper alone."""
    pkg = os.path.join(REPO, "ompi_release_tpu")
    holders = []
    for root, _dirs, files in os.walk(pkg):
        for fn in files:
            if fn.endswith(".py"):
                path = os.path.join(root, fn)
                if "TraceAnnotation" in open(path).read():
                    holders.append(os.path.relpath(path, REPO))
    assert holders == [SPANS_MODULE]
    wanted = {"COLL_CALL": "comm/communicator.py",
              "COLL_LAUNCH": "coll/plan.py", "COLL_COMPILE": "coll/driver.py",
              "NBC_WAIT": "coll/nbc.py",
              "PLAN_NATIVE_FIRE": "coll/native_exec.py",
              "PLAN_XCHG": "coll/plan.py", "HIER_D2H": "coll/hier.py",
              "HIER_H2D": "coll/hier.py", "HIER_ASSEMBLE": "coll/hier.py",
              "WIRE_STASH": "btl/nativewire.py",
              "PML_SEND": "p2p/pml.py", "PML_D2H": "p2p/pml.py",
              "PML_RECV_WAIT": "p2p/pml.py",
              "WIRE_P2P_SEND": "runtime/wire.py",
              "WIRE_P2P_PUMP": "runtime/wire.py",
              "PML_H2D": "runtime/wire.py",
              # ISSUE 34: the one-sided layer (a second site of
              # OSC_PROGRAM, the planned replay, is in osc/plan.py)
              "OSC_SYNC": "osc/wire_win.py", "OSC_PACK": "osc/wire_win.py",
              "OSC_D2H": "osc/wire_win.py",
              "OSC_REQUEST": "osc/wire_win.py",
              "OSC_REPLY_WAIT": "osc/wire_win.py",
              "OSC_UNPACK": "osc/wire_win.py",
              "OSC_APPLY": "osc/wire_win.py", "OSC_H2D": "osc/window.py",
              "OSC_PROGRAM": "osc/window.py",
              # ISSUE 36: the OpenSHMEM layer
              "SHMEM_QUIET": "oshmem/shmem.py",
              "SHMEM_DRAIN": "oshmem/shmem.py",
              "SHMEM_GET": "oshmem/shmem.py", "SHMEM_AMO": "oshmem/shmem.py",
              # ISSUE 38: what ompi.nbc.wait holds between two exchanges
              # (HIER_FOLD's second site, the exact-order fold, is in
              # coll/hier.py)
              "PLAN_ARRIVALS": "coll/native_exec.py",
              "HIER_PAD": "coll/hier_schedules.py",
              "HIER_FOLD": "coll/hier_schedules.py",
              # the device form of alltoallv
              "VCOLL_ALLTOALLV": "coll/vcoll.py"}
    import importlib.util  # obs/spans.py by path: the package pulls jax
    spec = importlib.util.spec_from_file_location(
        "_spans_only", os.path.join(REPO, SPANS_MODULE))
    spans = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(spans)
    # sixteen names since ISSUE 31, nine more since ISSUE 34, four since
    # ISSUE 36, three since ISSUE 38, each with a site of its own
    # (and one more: the device form of alltoallv's)
    assert len(spans.NAMES) == 33
    assert {getattr(spans, const) for const in wanted} == set(spans.NAMES)
    for const, rel in wanted.items():
        path = os.path.join(pkg, rel)
        tree = ast.parse(open(path).read())
        sites = [n for n in ast.walk(tree)
                 if isinstance(n, ast.Call) and n.args
                 and isinstance(n.args[0], ast.Attribute)
                 and n.args[0].attr == const]
        assert sites, f"{rel}: no span site for {const}"
        gated = {id(c) for node in ast.walk(tree)
                 if isinstance(node, ast.If) and _mentions_enabled(node.test)
                 for stmt in node.body for c in ast.walk(stmt)}
        assert not any(id(s) in gated for s in sites), (
            f"{rel}: a {const} span sits under an enabled gate")


def test_watchdog_arm_sites_are_gated_and_present():
    """The stall-watchdog arm sites (the new tracing layer's wait
    registry) must exist in the files that block on peers, and every
    one must sit under a ``_watchdog.enabled`` gate — enforced by the
    same scan (``arm`` is an EMIT_ATTR on a watchdog-ish base)."""
    armed = 0
    for rel in ("ompi_release_tpu/runtime/wire.py",
                "ompi_release_tpu/coll/hier.py",
                "ompi_release_tpu/osc/wire_win.py",
                "ompi_release_tpu/p2p/pml.py"):
        src = open(os.path.join(REPO, rel)).read()
        assert "_watchdog.enabled" in src and "_watchdog.arm" in src, (
            f"{rel}: expected gated stall-watchdog arm sites")
        armed += src.count("_watchdog.arm(")
    assert armed >= 6, f"expected >= 6 arm sites, found {armed}"


def test_gating_checker_catches_violations():
    """The checker itself must reject an ungated emit (guards against
    the static test rotting into a rubber stamp)."""
    bad = (
        "import time\n"
        "from .. import obs as _obs\n"
        "from ..mca import pvar\n"
        "_ok = pvar.counter('x')\n"
        "def hot(journal):\n"
        "    _ok.add()\n"                      # fine: module-level pvar
        "    journal.record('op', 'l', 0, 0)\n"  # VIOLATION: ungated
        "    local = pvar.counter('y')\n"        # VIOLATION: per-call
        "    local.add()\n"                      # VIOLATION: non-module
        "def hot2(ctr):\n"
        "    ctr.add()\n"  # VIOLATION: pvar smuggled in as an argument
        "def hot3():\n"
        "    seen = set()\n"
        "    seen.add(1)\n"     # fine: visibly a local container
        "    for q in ():\n"
        "        q.add(2)\n"    # fine: loop var
    )
    tree = ast.parse(bad)
    pvars = _module_pvars(tree)
    violations = []
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            _scan_stmts(node.body, False, pvars, violations, "bad.py",
                        _module_containers(tree)
                        | _bound_containers(node))
    assert len(violations) == 4, violations

    good = (
        "from .. import obs as _obs\n"
        "from ..mca import pvar\n"
        "_ok = pvar.counter('x')\n"
        "def hot(journal):\n"
        "    _ok.add()\n"
        "    if _obs.enabled:\n"
        "        journal.record('op', 'l', 0, 0)\n"
        "def hot2(journal):\n"
        "    if not _obs.enabled:\n"
        "        return 1\n"
        "    journal.record('op', 'l', 0, 0)\n"
    )
    tree = ast.parse(good)
    violations = []
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            _scan_stmts(node.body, False, _module_pvars(tree),
                        violations, "good.py",
                        _module_containers(tree)
                        | _bound_containers(node))
    assert not violations, violations

    # an ungated watchdog arm is a violation; a gated one is not
    wd = (
        "from ..obs import watchdog as _watchdog\n"
        "from ..mca import pvar\n"
        "_ok = pvar.counter('x')\n"
        "def bad_wait():\n"
        "    tok = _watchdog.arm('op')\n"          # VIOLATION: ungated
        "def good_wait():\n"
        "    tok = None\n"
        "    if _watchdog.enabled:\n"
        "        tok = _watchdog.arm('op')\n"
        "    if tok is not None:\n"
        "        _watchdog.disarm(tok)\n"
    )
    tree = ast.parse(wd)
    violations = []
    for node in ast.walk(tree):
        if isinstance(node, ast.FunctionDef):
            _scan_stmts(node.body, False, _module_pvars(tree),
                        violations, "wd.py",
                        _module_containers(tree)
                        | _bound_containers(node))
    assert len(violations) == 1 and "arm" in violations[0], violations
