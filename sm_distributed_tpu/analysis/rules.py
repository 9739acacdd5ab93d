"""The shipped smlint rules (docs/ANALYSIS.md has the catalog).

Every rule is a pure function over a parsed :class:`~.core.Project` and
ships a firing + passing fixture (``--self-check`` re-proves both, so a
rule that silently stops firing is itself a lint failure).

Rules:

- ``fence-gate``        — replicated write seams dominated by a fence guard
- ``failpoint-registry``— failpoints registered, called, documented, chaos-covered
- ``metrics-conventions``— ``sm_`` prefix, one kind per name, documented
- ``config-drift``      — SMConfig knobs <-> template <-> docs, both ways
- ``guarded-by``        — declared shared attrs mutated only under their lock
- ``broad-except``      — no silent ``except Exception`` swallows
- ``atomic-write``      — spool/lease/registry writes use unique-tmp + os.replace
- ``jit-compile-surface``— every jit/pjit/shard_map site declared in COMPILE_SURFACE
- ``retrace-hazard``    — raw shapes/lengths can't flow into static args unbucketed
- ``host-sync``         — device->host syncs in hot scoring modules are annotated
- ``dtype-flow``        — implicit-promotion hazards in NUMERICS-declaring modules
- ``masked-reduction``  — reductions over lattice-padded axes use the n_real helpers
- ``ulp-contract``      — every compile-surface site declares a test-backed contract

The local-variable taint walks (``fence-gate``, ``retrace-hazard``,
``dtype-flow``, ``masked-reduction``) all ride the shared forward-dataflow
engine in ``dataflow.py`` (ISSUE 15): one walker, per-rule source/
sanitizer predicates, single-level call summaries.
"""

from __future__ import annotations

import ast
import json
import re
import struct

from . import dataflow
from . import numerics as numerics_mod
from .core import Finding, Project, rule
from .dataflow import TaintTracker

# findings are created with rule/severity placeholders; core.Rule.run stamps
# the registered values over them
def _finding(mod, node, message: str) -> Finding:
    return Finding("", "", mod.path, getattr(node, "lineno", 0), message,
                   anchor=mod.anchor(node))


# ------------------------------------------------------------- AST helpers
def _attr_chain(node: ast.AST) -> str:
    """Dotted name of an attribute/name chain (``self.leases.check``), or
    "" when the expression is not a plain chain."""
    parts: list[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _call_name(call: ast.Call) -> str:
    """Terminal callee name: ``failpoint`` for both ``failpoint(...)`` and
    ``x.failpoint(...)``."""
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _const_str(node: ast.AST) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _subtree_strs(node: ast.AST) -> set[str]:
    return {n.value for n in ast.walk(node)
            if isinstance(n, ast.Constant) and isinstance(n.value, str)}


def _self_attr(node: ast.AST) -> str | None:
    """``X`` for an expression ``self.X``, else None."""
    if isinstance(node, ast.Attribute) and \
            isinstance(node.value, ast.Name) and node.value.id == "self":
        return node.attr
    return None


# =========================================================== 1. fence-gate
# the fenced write seams, anchored on their failpoint constants (PR 2/8
# placed a failpoint at exactly these seams, so the anchor cannot drift
# away from the write it marks)
_FENCED_FAILPOINTS = {
    "spool.complete": "spool complete (running/ -> done/)",
    "sched.retry_publish": "retry republish into pending/",
}
# terminal-spool dirs whose writes are dead-letter/quarantine seams
_TERMINAL_DIRS = ("failed", "quarantine")


def _terminal_dir_source(node: ast.AST) -> bool:
    """Taint source for the fence-gate walk: a string constant naming a
    terminal spool directory (the same subtree-string test the rule's
    original in-line walk applied to assignment RHSs)."""
    return isinstance(node, ast.Constant) and node.value in _TERMINAL_DIRS
# storage-layer commits gated at their CALL SITE (the storage module itself
# is the layer below the fence; its callers own the guard)
_GATED_CALLS = ("finish_job",)
_FENCE_GUARDS = ("fence", "_fence_ok")

_FENCE_FIXTURE_FAIL = {
    "sm_distributed_tpu/service/x.py": (
        "from u import register_failpoint, failpoint\n"
        "FP_C = register_failpoint('spool.complete', 'seam')\n"
        "class S:\n"
        "    def _finish(self, claimed):\n"
        "        failpoint(FP_C, path=claimed)\n"
        "        move(claimed)\n"
        "    def _dead_letter(self, claimed):\n"
        "        (self.root / 'failed' / claimed.name).write_text('x')\n"
        "    def _commit(self):\n"
        "        self.ledger.finish_job(1)\n"
    ),
}
_FENCE_FIXTURE_PASS = {
    "sm_distributed_tpu/service/x.py": (
        "from u import register_failpoint, failpoint\n"
        "FP_C = register_failpoint('spool.complete', 'seam')\n"
        "class S:\n"
        "    def _finish(self, claimed, rec):\n"
        "        if not self._fence_ok(rec, 'complete'):\n"
        "            return\n"
        "        failpoint(FP_C, path=claimed)\n"
        "        move(claimed)\n"
        "    def _dead_letter(self, claimed, rec):\n"
        "        if not self._fence_ok(rec, 'dead_letter'):\n"
        "            return\n"
        "        dst = self.root / 'failed' / claimed.name\n"
        "        dst.write_text('x')\n"
        "    def _commit(self):\n"
        "        if self.fence is not None:\n"
        "            self.fence()\n"
        "        self.ledger.finish_job(1)\n"
    ),
}


def _fp_const_map(project: Project) -> dict[str, str]:
    """{constant name: failpoint name} from every
    ``FP_X = register_failpoint("name", ...)`` assignment."""
    out: dict[str, str] = {}
    for mod in project.modules:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                    isinstance(node.targets[0], ast.Name) and \
                    isinstance(node.value, ast.Call) and \
                    _call_name(node.value) == "register_failpoint" and \
                    node.value.args:
                name = _const_str(node.value.args[0])
                if name:
                    out[node.targets[0].id] = name
    return out


@rule("fence-gate", severity="error",
      doc="Replicated write seams (spool complete/republish, dead-letter/"
          "quarantine writes, result store, ledger commit) must be "
          "dominated by a fence guard (LeaseStore.check via _fence_ok or "
          "a JobContext/SearchJob fence call) in the same function.",
      fixture_fail=_FENCE_FIXTURE_FAIL, fixture_pass=_FENCE_FIXTURE_PASS)
def fence_gate(project: Project):
    fp_names = _fp_const_map(project)
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue                  # scripts/benches drive, they don't own
                                      # replicated spool state
        if mod.path.endswith("engine/storage.py"):
            continue                  # the layer below the gate: its callers
                                      # (SearchJob, scheduler) own the guard
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            guards: list[int] = []    # linenos of fence-guard calls
            seams: list[tuple[ast.AST, str]] = []
            # shared dataflow engine (ISSUE 15): locals assigned from
            # expressions naming a terminal dir become tainted paths
            taint = TaintTracker(source=_terminal_dir_source)
            for node in taint.walk(mod, fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _call_name(node)
                if callee in _FENCE_GUARDS:
                    guards.append(node.lineno)
                elif callee == "check" and isinstance(node.func, ast.Attribute) \
                        and "leases" in _attr_chain(node.func):
                    guards.append(node.lineno)
                elif callee == "failpoint" and node.args and \
                        isinstance(node.args[0], ast.Name):
                    seam = _FENCED_FAILPOINTS.get(
                        fp_names.get(node.args[0].id, ""))
                    if seam:
                        seams.append((node, seam))
                elif callee == "write_text" and isinstance(node.func, ast.Attribute):
                    recv = node.func.value
                    hit = _subtree_strs(recv) & set(_TERMINAL_DIRS)
                    if not hit and isinstance(recv, ast.Name) and \
                            recv.id in taint.names:
                        hit = {"(tainted path)"}
                    if hit:
                        seams.append(
                            (node, f"terminal-spool write ({sorted(hit)[0]})"))
                elif callee == "replace" and \
                        _attr_chain(node.func) == "os.replace" and any(
                            _subtree_strs(a) & set(_TERMINAL_DIRS) or (
                                isinstance(a, ast.Name) and a.id in taint.names)
                            for a in node.args):
                    seams.append((node, "terminal-spool move"))
                elif callee in _GATED_CALLS:
                    seams.append((node, f"ledger commit ({callee})"))
                elif callee == "store" and isinstance(node.func, ast.Attribute) \
                        and isinstance(node.func.value, ast.Attribute) and \
                        node.func.value.attr == "store":
                    seams.append((node, "result store (store.store)"))
            for node, what in seams:
                if not any(g <= node.lineno for g in guards):
                    yield _finding(
                        mod, node,
                        f"{what} is not dominated by a fence guard "
                        f"(_fence_ok / fence() / leases.check) in "
                        f"{mod.qualname(node) or 'module scope'}")


# ==================================================== 2. failpoint-registry
_FPREG_FIXTURE_FAIL = {
    "sm_distributed_tpu/x.py": (
        "from u import register_failpoint, failpoint\n"
        "FP_A = register_failpoint('seam.a', 'covered')\n"
        "FP_DEAD = register_failpoint('seam.dead', 'never called')\n"
        "def f(p):\n"
        "    failpoint(FP_A, path=p)\n"
        "    failpoint(FP_GHOST)\n"
    ),
    "aux": {"docs/RECOVERY.md": "only `seam.a` is documented here\n",
            "scripts/chaos_sweep.py": "SCENARIOS = []\n"},
}
_FPREG_FIXTURE_PASS = {
    "sm_distributed_tpu/x.py": (
        "from u import register_failpoint, failpoint\n"
        "FP_A = register_failpoint('seam.a', 'covered')\n"
        "def f(p):\n"
        "    failpoint(FP_A, path=p)\n"
    ),
    "aux": {"docs/RECOVERY.md": "`seam.a` does X\n",
            "scripts/chaos_sweep.py": "Scenario('seam.a', ...)\n"},
}


@rule("failpoint-registry", severity="error",
      doc="Every registered failpoint must have >=1 call site (no dead "
          "entries), be documented in docs/RECOVERY.md, and be covered by "
          "a chaos_sweep scenario; every failpoint() call site must "
          "reference a registered constant.  Subsumes chaos_sweep "
          "--check-docs.",
      fixture_fail=_FPREG_FIXTURE_FAIL, fixture_pass=_FPREG_FIXTURE_PASS)
def failpoint_registry(project: Project):
    fp_names = _fp_const_map(project)
    registered: dict[str, tuple] = {}   # name -> (mod, node)
    called: set[str] = set()
    for mod in project.modules:
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node) == "register_failpoint" and node.args:
                name = _const_str(node.args[0])
                if not name:
                    yield _finding(mod, node,
                                   "register_failpoint name must be a "
                                   "string literal")
                elif name in registered:
                    yield _finding(
                        mod, node,
                        f"failpoint {name!r} registered twice (also at "
                        f"{registered[name][0].path}:"
                        f"{registered[name][1].lineno})")
                else:
                    registered[name] = (mod, node)
            elif _call_name(node) == "failpoint" and node.args and \
                    mod.path != "sm_distributed_tpu/utils/failpoints.py":
                arg = node.args[0]
                name = _const_str(arg) or (
                    fp_names.get(arg.id) if isinstance(arg, ast.Name)
                    else None)
                if name is None:
                    yield _finding(
                        mod, node,
                        "failpoint() called with an argument that does not "
                        "resolve to a register_failpoint constant")
                else:
                    called.add(name)
    recovery = project.read("docs/RECOVERY.md") or ""
    chaos_mod = project.module("scripts/chaos_sweep.py")
    chaos_src = chaos_mod.source if chaos_mod else (
        project.read("scripts/chaos_sweep.py") or "")
    for name, (mod, node) in sorted(registered.items()):
        if name not in called:
            yield _finding(mod, node,
                           f"failpoint {name!r} is registered but never "
                           f"reached by a failpoint() call site (dead entry)")
        if name not in recovery:
            yield _finding(mod, node,
                           f"failpoint {name!r} is not documented in "
                           f"docs/RECOVERY.md")
        if name not in chaos_src:
            yield _finding(mod, node,
                           f"failpoint {name!r} has no chaos_sweep scenario")


# ================================================== 3. metrics-conventions
_METRIC_KINDS = ("counter", "gauge", "histogram")
_METRIC_NAME_RE = re.compile(r"^sm_[a-z0-9_]+$")
_METRIC_DOCS = ("docs/OBSERVABILITY.md", "docs/SERVICE.md")

_METRICS_FIXTURE_FAIL = {
    "sm_distributed_tpu/x.py": (
        "def f(m):\n"
        "    m.counter('jobs_total', 'no prefix').inc()\n"
        "    m.gauge('sm_thing', 'kind conflict').set(1)\n"
        "    m.counter('sm_thing', 'kind conflict').inc()\n"
        "    m.counter('sm_undocumented_total', 'not in docs').inc()\n"
    ),
    "aux": {"docs/OBSERVABILITY.md": "`sm_thing` is documented\n"},
}
_METRICS_FIXTURE_PASS = {
    "sm_distributed_tpu/x.py": (
        "def f(m):\n"
        "    m.counter('sm_jobs_total', 'documented').inc()\n"
    ),
    "aux": {"docs/OBSERVABILITY.md": "`sm_jobs_total` counts jobs\n"},
}


@rule("metrics-conventions", severity="error",
      doc="Every metric registered by literal name must be sm_-prefixed, "
          "keep ONE kind (counter/gauge/histogram) across the tree, and be "
          "documented in docs/OBSERVABILITY.md or docs/SERVICE.md.",
      fixture_fail=_METRICS_FIXTURE_FAIL, fixture_pass=_METRICS_FIXTURE_PASS)
def metrics_conventions(project: Project):
    docs = project.doc_text(*_METRIC_DOCS)
    seen: dict[str, tuple[str, object, object]] = {}  # name -> (kind, mod, node)
    for mod in project.modules:
        for node in ast.walk(mod.tree):
            if not (isinstance(node, ast.Call) and
                    _call_name(node) in _METRIC_KINDS and node.args):
                continue
            name = _const_str(node.args[0])
            if name is None:
                continue              # dynamic names (registry internals)
            kind = _call_name(node)
            if not _METRIC_NAME_RE.match(name):
                yield _finding(mod, node,
                               f"metric {name!r} violates the sm_ naming "
                               f"convention (^sm_[a-z0-9_]+$)")
            prior = seen.get(name)
            if prior is None:
                seen[name] = (kind, mod, node)
                if name not in docs:
                    yield _finding(
                        mod, node,
                        f"metric {name!r} is not documented in "
                        f"{' or '.join(_METRIC_DOCS)}")
            elif prior[0] != kind:
                yield _finding(
                    mod, node,
                    f"metric {name!r} registered as {kind} here but as "
                    f"{prior[0]} at {prior[1].path}:{prior[2].lineno} — "
                    f"one name, one kind")


# ========================================================= 4. config-drift
_CONFIG_MODULE = "utils/config.py"
_TEMPLATES = {"SMConfig": "conf/config.json.template",
              "DSConfig": "conf/ds_config.json.template"}

_CONFIG_FIXTURE_FAIL = {
    "sm_distributed_tpu/utils/config.py": (
        "from dataclasses import dataclass, field\n"
        "@dataclass\n"
        "class SubConfig:\n"
        "    knob_a: int = 1\n"
        "@dataclass\n"
        "class SMConfig:\n"
        "    backend: str = 'x'\n"
        "    missing_from_template: int = 0\n"
        "    sub: SubConfig = field(default_factory=SubConfig)\n"
    ),
    "aux": {
        "conf/config.json.template": json.dumps(
            {"backend": "x", "sub": {"knob_a": 1, "ghost_key": 2}}),
        "README.md": "backend knob_a ghost_key docs\n",
    },
}
_CONFIG_FIXTURE_PASS = {
    "sm_distributed_tpu/utils/config.py": (
        "from dataclasses import dataclass, field\n"
        "@dataclass\n"
        "class SMConfig:\n"
        "    backend: str = 'x'\n"
    ),
    "aux": {"conf/config.json.template": json.dumps({"backend": "x"}),
            "README.md": "the backend knob is documented\n"},
}


def _dataclass_fields(mod) -> dict[str, list[tuple[str, str, int]]]:
    """{ClassName: [(field, annotation_name, lineno)]} for @dataclass
    classes (ClassVar and properties excluded)."""
    out: dict[str, list[tuple[str, str, int]]] = {}
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.ClassDef):
            continue
        if not any("dataclass" in _attr_chain(d) or (
                isinstance(d, ast.Call) and "dataclass" in _attr_chain(d.func))
                for d in node.decorator_list):
            continue
        fields = []
        for stmt in node.body:
            if not (isinstance(stmt, ast.AnnAssign) and
                    isinstance(stmt.target, ast.Name)):
                continue
            ann = stmt.annotation
            ann_name = ann.id if isinstance(ann, ast.Name) else (
                _const_str(ann) or "")
            if "ClassVar" in ast.dump(ann):
                continue
            fields.append((stmt.target.id, ann_name.strip('"'), stmt.lineno))
        out[node.name] = fields
    return out


def _knob_tree(classes: dict, cls: str, prefix: str = "") -> dict[str, int]:
    """{dotted knob path: lineno}; nested dataclass fields recurse."""
    out: dict[str, int] = {}
    for name, ann, lineno in classes.get(cls, []):
        ann = ann.strip("'\" ")
        if ann in classes:
            out.update(_knob_tree(classes, ann, prefix + name + "."))
        else:
            out[prefix + name] = lineno
    return out


def _template_keys(data: dict, prefix: str = "") -> set[str]:
    out: set[str] = set()
    for k, v in data.items():
        if k.startswith("__"):
            continue                  # template comment keys
        if isinstance(v, dict):
            out |= _template_keys(v, prefix + k + ".")
        else:
            out.add(prefix + k)
    return out


@rule("config-drift", severity="error",
      doc="Every SMConfig/DSConfig knob must appear in its conf/*.template "
          "and in the docs (docs/*.md or README), and every template key "
          "must be a real knob.",
      fixture_fail=_CONFIG_FIXTURE_FAIL, fixture_pass=_CONFIG_FIXTURE_PASS)
def config_drift(project: Project):
    mod = project.module(_CONFIG_MODULE)
    if mod is None:
        return
    classes = _dataclass_fields(mod)
    docs = [project.read("README.md") or ""]
    if project.root is not None:
        docs += [p.read_text() for p in sorted(
            (project.root / "docs").glob("*.md"))]
    docs += [v for k, v in project.aux.items()
             if k.startswith("docs/") and k != "README.md"]
    doc_text = "\n".join(docs)
    for cls, tmpl_path in _TEMPLATES.items():
        if cls not in classes:
            continue
        knobs = _knob_tree(classes, cls)
        raw = project.read(tmpl_path)
        if raw is None:
            yield _finding(mod, mod.tree, f"missing template {tmpl_path}")
            continue
        tmpl = _template_keys(json.loads(raw))
        for knob, lineno in sorted(knobs.items()):
            if knob not in tmpl:
                yield Finding("", "", mod.path, lineno,
                              f"{cls} knob {knob!r} is missing from "
                              f"{tmpl_path}", anchor=f"{cls}.{knob}")
            leaf = knob.split(".")[-1]
            if leaf not in doc_text:
                yield Finding("", "", mod.path, lineno,
                              f"{cls} knob {knob!r} is not documented "
                              f"anywhere under docs/ or README.md",
                              anchor=f"{cls}.{knob}.docs")
        for key in sorted(tmpl - set(knobs)):
            yield Finding("", "", mod.path, 0,
                          f"{tmpl_path} key {key!r} is not a {cls} knob "
                          f"(typo or removed config?)",
                          anchor=f"{cls}.template.{key}")


# ============================================================ 5. guarded-by
_MUTATORS = {"append", "extend", "insert", "remove", "pop", "popitem",
             "clear", "update", "add", "discard", "setdefault",
             "move_to_end", "appendleft", "popleft", "sort", "reverse"}

_GUARDED_FIXTURE_FAIL = {
    "sm_distributed_tpu/x.py": (
        "import threading\n"
        "class C:\n"
        "    _GUARDED_BY = {'_items': '_lock', '_count': '_lock'}\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._items = []\n"
        "        self._count = 0\n"
        "    def bad(self, x):\n"
        "        self._items.append(x)\n"
        "        self._count += 1\n"
    ),
}
_GUARDED_FIXTURE_PASS = {
    "sm_distributed_tpu/x.py": (
        "import threading\n"
        "class C:\n"
        "    _GUARDED_BY = {'_items': '_lock', '_count': '_lock'}\n"
        "    def __init__(self):\n"
        "        self._lock = threading.Lock()\n"
        "        self._items = []\n"
        "        self._count = 0\n"
        "    def good(self, x):\n"
        "        with self._lock:\n"
        "            self._items.append(x)\n"
        "            self._count += 1\n"
        "    def _drain_locked(self):\n"
        "        self._items.clear()\n"
    ),
}


def _guarded_decls(cls: ast.ClassDef) -> dict[str, str]:
    """The class's ``_GUARDED_BY = {attr: lock}`` declaration, if any."""
    for stmt in cls.body:
        targets = stmt.targets if isinstance(stmt, ast.Assign) else (
            [stmt.target] if isinstance(stmt, ast.AnnAssign) else [])
        if any(isinstance(t, ast.Name) and t.id == "_GUARDED_BY"
               for t in targets) and isinstance(
                   getattr(stmt, "value", None), ast.Dict):
            out = {}
            for k, v in zip(stmt.value.keys, stmt.value.values):
                ks, vs = _const_str(k), _const_str(v)
                if ks and vs:
                    out[ks] = vs
            return out
    return {}


def _mutated_attr(node: ast.AST) -> str | None:
    """``X`` when ``node`` mutates ``self.X``: assignment/augassign/del of
    ``self.X`` (or a subscript of it), or a mutating method call on it."""
    if isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Delete)):
        targets = getattr(node, "targets", None) or \
            [getattr(node, "target", None)]
        for t in targets:
            if t is None:
                continue
            base = t.value if isinstance(t, ast.Subscript) else t
            attr = _self_attr(base)
            if attr:
                return attr
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
            and node.func.attr in _MUTATORS:
        return _self_attr(node.func.value)
    return None


def _holds_lock(mod, node: ast.AST, lock: str) -> bool:
    """Is ``node`` lexically inside ``with self.<lock>:``?"""
    for anc in mod.ancestors(node):
        if isinstance(anc, (ast.With, ast.AsyncWith)):
            for item in anc.items:
                if _self_attr(item.context_expr) == lock:
                    return True
    return False


@rule("guarded-by", severity="error",
      doc="Attributes declared in a class's _GUARDED_BY registry may only "
          "be mutated inside `with self.<lock>:` — except in __init__ "
          "(happens-before publication) and in methods named *_locked "
          "(documented caller-holds-lock convention).",
      fixture_fail=_GUARDED_FIXTURE_FAIL, fixture_pass=_GUARDED_FIXTURE_PASS)
def guarded_by(project: Project):
    for mod in project.modules:
        for cls in ast.walk(mod.tree):
            if not isinstance(cls, ast.ClassDef):
                continue
            decls = _guarded_decls(cls)
            if not decls:
                continue
            for fn in cls.body:
                if not isinstance(fn, (ast.FunctionDef,
                                       ast.AsyncFunctionDef)):
                    continue
                if fn.name == "__init__" or fn.name.endswith("_locked"):
                    continue
                for node in ast.walk(fn):
                    attr = _mutated_attr(node)
                    if attr is None or attr not in decls:
                        continue
                    lock = decls[attr]
                    if not _holds_lock(mod, node, lock):
                        yield _finding(
                            mod, node,
                            f"{cls.name}.{attr} is declared guarded by "
                            f"self.{lock} but is mutated in {fn.name}() "
                            f"without holding it")


# ========================================================== 6. atomic-write
# directories whose contents other processes/threads read CONCURRENTLY by
# glob: a non-atomic write here is a torn-JSON/BadZipFile waiting for a
# reader (the spool states, the fenced-lease files, the replica registry).
# The convention (PR 1/2/8): write a unique tmp name, then os.replace /
# Path.replace into place.
_AW_DIRS = ("pending", "running", "done", "failed", "quarantine",
            "leases", "replicas")
_AW_WRITE_METHODS = ("write_text", "write_bytes")

_AW_FIXTURE_FAIL = {
    "sm_distributed_tpu/service/x.py": (
        "class S:\n"
        "    def bad_direct(self, msg_id, data):\n"
        "        (self.root / 'failed' / msg_id).write_text(data)\n"
        "    def bad_open(self, msg_id, data):\n"
        "        dst = self.root / 'pending' / msg_id\n"
        "        with open(dst, 'w') as f:\n"
        "            f.write(data)\n"
        "    def bad_tmp_no_replace(self, msg_id, data):\n"
        "        tmp = self.root / 'pending' / f'.{msg_id}.tmp'\n"
        "        tmp.write_text(data)\n"
    ),
}
_AW_FIXTURE_PASS = {
    "sm_distributed_tpu/service/x.py": (
        "import os\n"
        "class S:\n"
        "    def good(self, msg_id, data):\n"
        "        tmp = self.root / 'pending' / f'.{msg_id}.tmp'\n"
        "        tmp.write_text(data)\n"
        "        os.replace(tmp, self.root / 'pending' / f'{msg_id}.json')\n"
        "    def good_path_replace(self, msg_id, data):\n"
        "        tmp = self.root / 'leases' / f'.{msg_id}.tmp'\n"
        "        tmp.write_text(data)\n"
        "        tmp.replace(self.root / 'leases' / f'{msg_id}.json')\n"
        "    def reader(self):\n"
        "        return (self.root / 'done' / 'x.json').read_text()\n"
    ),
}


def _open_write_mode(call: ast.Call) -> bool:
    """``open(..., 'w'/'wb'/...)`` — any truncating/creating text/binary
    write mode (append keeps prior bytes but still tears concurrent
    readers; included)."""
    mode = None
    if len(call.args) >= 2:
        mode = _const_str(call.args[1])
    for kw in call.keywords:
        if kw.arg == "mode":
            mode = _const_str(kw.value)
    return bool(mode) and any(c in mode for c in "wax")


@rule("atomic-write", severity="error",
      doc="Any open-for-write landing in a concurrently-globbed spool/"
          "lease/registry directory (pending, running, done, failed, "
          "quarantine, leases, replicas) must follow the unique-tmp + "
          "os.replace convention: the write target must be a tmp name and "
          "the same function must replace it into place afterwards.",
      fixture_fail=_AW_FIXTURE_FAIL, fixture_pass=_AW_FIXTURE_PASS)
def atomic_write(project: Project):
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue                  # scripts/benches are single-actor
                                      # drivers over their own sandboxes
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # locals assigned from expressions naming a protected dir;
            # value = whether the SAME expression names a tmp component
            tainted: dict[str, bool] = {}
            replaces: list[int] = []
            writes: list[tuple[ast.AST, str, bool]] = []
            for node in ast.walk(fn):
                if mod.enclosing_function(node) is not fn and node is not fn:
                    continue          # skip nested defs/lambdas
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name):
                    strs = _subtree_strs(node.value)
                    if strs & set(_AW_DIRS):
                        tainted[node.targets[0].id] = any(
                            "tmp" in s for s in strs)
                if not isinstance(node, ast.Call):
                    continue
                callee = _call_name(node)
                if callee == "replace":
                    replaces.append(node.lineno)
                    continue
                target = None
                if callee in _AW_WRITE_METHODS and \
                        isinstance(node.func, ast.Attribute):
                    target = node.func.value
                elif callee == "open" and node.args and \
                        _open_write_mode(node):
                    target = node.args[0]
                if target is None:
                    continue
                strs = _subtree_strs(target)
                is_tmp = any("tmp" in s for s in strs)
                hit = bool(strs & set(_AW_DIRS))
                if not hit and isinstance(target, ast.Name) and \
                        target.id in tainted:
                    hit = True
                    is_tmp = is_tmp or tainted[target.id]
                if hit:
                    writes.append((node, callee, is_tmp))
            for node, callee, is_tmp in writes:
                if not is_tmp:
                    yield _finding(
                        mod, node,
                        f"{callee}() writes directly into a concurrently-"
                        f"globbed spool/lease/registry directory — use a "
                        f"unique tmp name + os.replace (torn writes become "
                        f"reader-visible garbage)")
                elif not any(ln > node.lineno for ln in replaces):
                    yield _finding(
                        mod, node,
                        f"{callee}() writes a tmp file in a spool/lease/"
                        f"registry directory but "
                        f"{mod.qualname(node) or 'module scope'} never "
                        f"os.replace()s it into place — half a convention "
                        f"leaks orphan tmps")


# ==================================================== 7. jit-compile-surface
# The cold-start invariant (ROADMAP item 1): every jax.jit / pjit /
# shard_map call site must be covered by a module-level COMPILE_SURFACE
# registry (analysis/surface.py) naming its shape-bucket policy, and must
# declare its statics (static_argnames/static_argnums or donation) or be
# registered as statics=none / statics=closure(...).  The runtime half is
# the retrace tracer + scripts/compile_census.py.
_JIT_CALLEES = ("jit", "pjit")
_STATIC_KWARGS = ("static_argnames", "static_argnums",
                  "donate_argnums", "donate_argnames")
_POLICY_TOKENS = ("statics=", "buckets=")     # analysis/surface.POLICY_TOKENS

_JCS_FIXTURE_FAIL = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax\n"
        "from functools import partial\n"
        "def score(x, *, b):\n"
        "    return x\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._fn = jax.jit(partial(score, b=1))\n"
    ),
}
_JCS_FIXTURE_PASS = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax\n"
        "from functools import partial\n"
        "from ..analysis.surface import compile_surface\n"
        "COMPILE_SURFACE = compile_surface(__name__, {\n"
        "    'score': 'statics=b; buckets=b padded to formula_batch',\n"
        "    'plain': 'statics=none; buckets=single static shape',\n"
        "})\n"
        "def score(x, *, b):\n"
        "    return x\n"
        "def plain(x):\n"
        "    return x\n"
        "class B:\n"
        "    def __init__(self):\n"
        "        self._fn = jax.jit(partial(score, b=1),\n"
        "                           static_argnames=('b',))\n"
        "        self._fp = jax.jit(plain)\n"
    ),
}


def _surface_decl(mod) -> tuple[dict[str, tuple[str, int]] | None, int]:
    """The module's ``COMPILE_SURFACE = compile_surface(_, {...})``
    declaration: ({site: (policy, lineno)}, decl lineno), or (None, 0)."""
    for node in mod.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1 and
                isinstance(node.targets[0], ast.Name) and
                node.targets[0].id == "COMPILE_SURFACE"):
            continue
        if not (isinstance(node.value, ast.Call) and
                _call_name(node.value) == "compile_surface" and
                len(node.value.args) >= 2 and
                isinstance(node.value.args[1], ast.Dict)):
            return {}, node.lineno    # declared but not the literal grammar
        out = {}
        for k, v in zip(node.value.args[1].keys,
                        node.value.args[1].values):
            ks, vs = _const_str(k), _const_str(v)
            if ks is not None:
                out[ks] = (vs or "", getattr(k, "lineno", node.lineno))
        return out, node.lineno
    return None, 0


def _jit_sites(mod):
    """Yield ``(call node, site name, static names | None, kind)`` for
    every jit/pjit/shard_map call site in ``mod``.  ``static names`` is
    the literal static_argnames tuple when given, () when a static/donate
    kwarg exists but is not a literal name tuple, None when the call
    declares no statics at all.  ``kind``: "jit" or "shard_map"."""
    for node in ast.walk(mod.tree):
        if not isinstance(node, ast.Call):
            continue
        callee = _call_name(node)
        kws = node.keywords
        kind = None
        if callee in _JIT_CALLEES:
            kind = "jit"
        elif callee == "shard_map":
            kind = "shard_map"
        elif callee == "partial" and node.args and \
                _attr_chain(node.args[0]).split(".")[-1] in _JIT_CALLEES:
            kind = "jit"              # @partial(jax.jit, static_argnames=...)
        if kind is None:
            continue
        statics: tuple | None = None
        for kw in kws:
            if kw.arg in _STATIC_KWARGS:
                names = []
                if isinstance(kw.value, (ast.Tuple, ast.List)):
                    names = [s for s in map(_const_str, kw.value.elts)
                             if s is not None]
                statics = tuple(sorted(set(list(statics or ()) + names)))
        if kind == "shard_map" and statics is None and any(
                kw.arg in ("in_specs", "out_specs") for kw in kws):
            statics = ()              # specs are the shard_map declaration
        yield node, _jit_site_name(mod, node), statics, kind


def _jit_site_name(mod, node: ast.Call) -> str:
    """Stable registry key for one jit site: the wrapped function's name
    when resolvable (decorated def, ``jax.jit(f)``, ``jax.jit(partial(f,
    ...))`` — plain or the name-preserving ``named_partial`` variant —
    ``jax.jit(shard_map(f, ...))``), else the assignment target
    (``self._fn = jax.jit(...)`` -> ``_fn``), else the enclosing
    qualname."""
    parent = mod.parents.get(node)
    # decorator (plain or partial-form): key on the decorated function
    if isinstance(parent, (ast.FunctionDef, ast.AsyncFunctionDef)) and \
            node in parent.decorator_list:
        return parent.name
    wrapped = node.args[0] if node.args else None
    for _ in range(3):                # unwrap partial(...)/shard_map(...)
        if isinstance(wrapped, ast.Call) and \
                _call_name(wrapped) in ("partial", "named_partial",
                                        "shard_map") and \
                wrapped.args:
            wrapped = wrapped.args[0]
        else:
            break
    if isinstance(wrapped, ast.Name):
        return wrapped.id
    if isinstance(parent, ast.Assign) and len(parent.targets) == 1:
        t = parent.targets[0]
        if isinstance(t, ast.Attribute):
            return t.attr
        if isinstance(t, ast.Name):
            return t.id
    return mod.qualname(node) or "<module>"


def _policy_statics(policy: str) -> str:
    """The ``statics=...`` clause of a policy string ("" when absent)."""
    for part in policy.split(";"):
        part = part.strip()
        if part.startswith("statics="):
            return part[len("statics="):].strip()
    return ""


@rule("jit-compile-surface", severity="error",
      doc="Every jax.jit / pjit / shard_map call site must be covered by "
          "a module-level COMPILE_SURFACE = compile_surface(__name__, "
          "{site: policy}) registry (analysis/surface.py) whose policy "
          "carries statics= and buckets= clauses; statics declared at the "
          "call site must match the registered statics, and sites with no "
          "static/donate declaration must register statics=none or "
          "statics=closure(...).  Dead registry entries are findings too.",
      fixture_fail=_JCS_FIXTURE_FAIL, fixture_pass=_JCS_FIXTURE_PASS)
def jit_compile_surface(project: Project):
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue                  # scripts/benches drive declared
                                      # surfaces; they don't own one
        sites = list(_jit_sites(mod))
        if not sites:
            continue
        decl, decl_line = _surface_decl(mod)
        if decl is None:
            yield Finding(
                "", "", mod.path, sites[0][0].lineno,
                f"module has {len(sites)} jit/shard_map call site(s) but "
                f"no COMPILE_SURFACE = compile_surface(__name__, "
                f"{{...}}) registry declaring its shape-bucket policy",
                anchor="COMPILE_SURFACE")
            continue
        used: set[str] = set()
        for node, site, statics, kind in sites:
            entry = decl.get(site)
            if entry is None:
                yield _finding(
                    mod, node,
                    f"{kind} call site {site!r} is not registered in this "
                    f"module's COMPILE_SURFACE (declare its statics and "
                    f"shape-bucket policy)")
                continue
            used.add(site)
            policy, _ln = entry
            missing = [t for t in _POLICY_TOKENS if t not in policy]
            if missing:
                yield _finding(
                    mod, node,
                    f"COMPILE_SURFACE entry {site!r} lacks the "
                    f"{'/'.join(missing)} clause(s) of the policy grammar")
                continue
            declared = _policy_statics(policy)
            if statics is None and not (
                    declared == "none" or declared.startswith("closure(")):
                yield _finding(
                    mod, node,
                    f"{kind} call site {site!r} declares no static_argnames"
                    f"/donation but its COMPILE_SURFACE entry says "
                    f"statics={declared!r} — declare the statics at the "
                    f"call site or register statics=none / closure(...)")
            elif statics:
                reg = tuple(sorted(s.strip() for s in declared.split(",")
                                   if s.strip()))
                if reg and reg != statics:
                    yield _finding(
                        mod, node,
                        f"{site!r} statics drift: call site declares "
                        f"{sorted(statics)} but COMPILE_SURFACE registers "
                        f"statics={declared!r}")
        for site, (policy, lineno) in sorted(decl.items()):
            if site not in used:
                yield Finding(
                    "", "", mod.path, lineno,
                    f"COMPILE_SURFACE entry {site!r} matches no jit/"
                    f"shard_map call site (dead entry — remove it or fix "
                    f"the site name)", anchor=f"COMPILE_SURFACE.{site}")


def compile_surface_census(project: Project) -> dict[str, int]:
    """Static totals for the perf_sentinel-comparable smlint artifact:
    jit/shard_map call sites, registered COMPILE_SURFACE entries, and
    modules carrying a registry."""
    sites = entries = modules = 0
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue
        mod_sites = list(_jit_sites(mod))
        sites += len(mod_sites)
        decl, _ = _surface_decl(mod)
        if decl:
            modules += 1
            entries += len(decl)
    return {"sites": sites, "entries": entries, "modules": modules}


# ========================================================= 8. retrace-hazard
# Raw runtime-shape reads (`x.shape[...]`, `len(x)`, `x.size`) flowing
# into a jitted callable's STATIC argument mint one executable per
# distinct value — the unbounded-signature family behind r4's 81-308 s
# cold compiles.  Static values must pass a bucketing/padding helper
# first so every dataset size lands in a small closed set.
_BUCKET_HELPERS = ("shape_key", "window_chunks")

_RH_FIXTURE_FAIL = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax\n"
        "fn = jax.jit(score, static_argnames=('b', 'w'))\n"
        "def go(x):\n"
        "    return fn(x, b=x.shape[0])\n"
        "def go2(x):\n"
        "    n = len(x)\n"
        "    return fn(x, w=n)\n"
    ),
}
_RH_FIXTURE_PASS = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax\n"
        "fn = jax.jit(score, static_argnames=('b', 'w'))\n"
        "def go(x):\n"
        "    return fn(x, b=size_bucket(x.shape[0]))\n"
        "def go2(x):\n"
        "    n = round_up(len(x), 256)\n"
        "    return fn(x, w=n)\n"
    ),
}


def _is_shape_source(node: ast.AST) -> bool:
    """A raw runtime-shape read: ``.shape`` / ``.size`` attribute access
    or a ``len(...)`` call."""
    if isinstance(node, ast.Attribute) and node.attr in ("shape", "size"):
        return True
    return isinstance(node, ast.Call) and _call_name(node) == "len"


def _is_bucketing_call(node: ast.AST) -> bool:
    """A call through a recognized bucketing/padding helper: name contains
    ``bucket``/``round``/``pad``, or one of the named shape-plan helpers."""
    if not isinstance(node, ast.Call):
        return False
    callee = _call_name(node)
    return (callee in _BUCKET_HELPERS or
            any(t in callee for t in ("bucket", "round", "pad")))


@rule("retrace-hazard", severity="error",
      doc="Raw runtime-shape reads (.shape / .size / len()) must not flow "
          "into a jitted callable's static arguments (the kwarg names a "
          "module's jit sites declare via static_argnames) without "
          "passing a bucketing/padding helper — one executable per "
          "distinct value is the unbounded cold-compile family.",
      fixture_fail=_RH_FIXTURE_FAIL, fixture_pass=_RH_FIXTURE_PASS)
def retrace_hazard(project: Project):
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue
        # the module's static-arg namespace: every literal static name any
        # of its jit sites declares (per-module scoping keeps a common
        # kwarg like `b` in OTHER modules out of the sink set)
        static_names: set[str] = set()
        for _node, _site, statics, _kind in _jit_sites(mod):
            static_names |= set(statics or ())
        if not static_names:
            continue
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            # shared dataflow engine (ISSUE 15): raw shape reads taint
            # locals; ONE bucketing call anywhere in an expression
            # sanitizes the whole expression (the legacy flat contract)
            taint = TaintTracker(source=_is_shape_source,
                                 sanitizer=_is_bucketing_call)
            for node in taint.walk(mod, fn):
                if not isinstance(node, ast.Call):
                    continue
                for kw in node.keywords:
                    if kw.arg not in static_names:
                        continue
                    if taint.expr_tainted(kw.value):
                        yield _finding(
                            mod, node,
                            f"static argument {kw.arg!r} receives a raw "
                            f"runtime shape (.shape/.size/len) without a "
                            f"bucketing/padding helper — every distinct "
                            f"value compiles a new executable "
                            f"(retrace hazard)")


# ============================================================== 9. host-sync
# Device->host synchronization points in the HOT scoring modules: each
# np.asarray/np.array/device_get/block_until_ready/.item() stalls the
# async dispatch pipeline, so every one must be a deliberate, argued
# fetch point — annotated `# smlint: host-sync-ok[reason]`.
_HS_MODULES_EXACT = ("models/msm_jax.py", "parallel/sharded.py")
_HS_NP_CALLS = ("asarray", "array", "ascontiguousarray")
_HS_METHOD_CALLS = ("block_until_ready", "item")

_HS_FIXTURE_FAIL = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import numpy as np\n"
        "import jax\n"
        "def score(fn, x):\n"
        "    out = fn(x)\n"
        "    out.block_until_ready()\n"
        "    v = float(fn(x)[0])\n"
        "    return np.asarray(out), v\n"
    ),
}
_HS_FIXTURE_PASS = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import numpy as np\n"
        "def score(fn, x):\n"
        "    out = fn(x)\n"
        "    # smlint: host-sync-ok[the designed per-group fetch point]\n"
        "    return np.asarray(out)\n"
        "def host_prep(rows):\n"
        "    return [r + 1 for r in rows]\n"
    ),
}


def _is_hot_module(path: str) -> bool:
    if any(path.endswith(m) for m in _HS_MODULES_EXACT):
        return True
    return "/ops/" in path and path.endswith("_jax.py")


def _host_sync_call(node: ast.Call) -> str | None:
    """The sync kind when ``node`` is a device->host synchronization:
    np.asarray/np.array/..., jax.device_get, .block_until_ready(),
    .item(), or float()/int() directly over a call result."""
    callee = _call_name(node)
    chain = _attr_chain(node.func)
    if callee in _HS_NP_CALLS and chain.split(".")[0] in ("np", "numpy"):
        return f"np.{callee}"
    if callee == "device_get" and "jax" in chain:
        return "jax.device_get"
    if callee in _HS_METHOD_CALLS and isinstance(node.func, ast.Attribute):
        return f".{callee}()"
    # float() directly over a call result forces the value to host; int()
    # is excluded — it is overwhelmingly host-side index arithmetic
    # (int(np.searchsorted(...))), not a device sync
    if callee == "float" and len(node.args) == 1 and \
            isinstance(node.args[0], (ast.Call, ast.Subscript)) and any(
            isinstance(n, ast.Call) for n in ast.walk(node.args[0])):
        return "float() on a call result"
    return None


@rule("host-sync", severity="error",
      doc="Device->host syncs (np.asarray / np.array / jax.device_get / "
          ".block_until_ready() / .item() / float() on a call result) in "
          "the hot scoring modules (models/msm_jax.py, parallel/"
          "sharded.py, ops/*_jax.py) must carry a `# smlint: "
          "host-sync-ok[reason]` annotation — each sync is a deliberate "
          "pipeline stall that must be argued, not an accident.",
      fixture_fail=_HS_FIXTURE_FAIL, fixture_pass=_HS_FIXTURE_PASS)
def host_sync(project: Project):
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/") or \
                not _is_hot_module(mod.path):
            continue
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = _host_sync_call(node)
            if kind is None:
                continue
            reason = mod.host_sync_reason(node.lineno)
            if reason:
                continue
            if reason == "":
                yield _finding(
                    mod, node,
                    f"host-sync-ok annotation for {kind} has an empty "
                    f"reason — the reason is the point")
            else:
                yield _finding(
                    mod, node,
                    f"{kind} in a hot scoring module is a device->host "
                    f"sync point — annotate `# smlint: host-sync-ok"
                    f"[reason]` (why this stall is deliberate) or move it "
                    f"off the hot path")


# ========================================================== 10. broad-except
_LOG_METHODS = {"debug", "info", "warning", "error", "exception", "critical",
                "log", "write"}

_BROAD_FIXTURE_FAIL = {
    "sm_distributed_tpu/x.py": (
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        pass\n"
        "    try:\n"
        "        g()\n"
        "    except:\n"
        "        return None\n"
    ),
}
_BROAD_FIXTURE_PASS = {
    "sm_distributed_tpu/x.py": (
        "from .logger import logger\n"
        "def f():\n"
        "    try:\n"
        "        g()\n"
        "    except Exception:\n"
        "        logger.warning('g failed', exc_info=True)\n"
        "    try:\n"
        "        g()\n"
        "    except Exception as exc:\n"
        "        record(exc)\n"
        "        raise\n"
        "    try:\n"
        "        g()\n"
        "    except (OSError, ValueError):\n"
        "        pass\n"
    ),
}


def _is_broad(handler: ast.ExceptHandler) -> bool:
    t = handler.type
    if t is None:
        return True                   # bare except:
    names = [t] if not isinstance(t, ast.Tuple) else list(t.elts)
    return any(isinstance(n, ast.Name) and
               n.id in ("Exception", "BaseException") for n in names)


def _handler_swallows(handler: ast.ExceptHandler) -> bool:
    """True when the body neither re-raises, nor logs, nor uses the bound
    exception (recording it somewhere counts as handling)."""
    for node in ast.walk(handler):
        if isinstance(node, ast.Raise):
            return False
        if isinstance(node, ast.Name) and handler.name and \
                node.id == handler.name and isinstance(node.ctx, ast.Load):
            return False
        if isinstance(node, ast.Call):
            callee = _call_name(node)
            chain = _attr_chain(node.func)
            if callee in _LOG_METHODS and ("logger" in chain or
                                           "logging" in chain or
                                           "stderr" in chain or
                                           "stdout" in chain):
                return False
            if callee in ("record_recovery", "format_exc", "print_exc"):
                return False
    return True


@rule("broad-except", severity="error",
      doc="No `except Exception` / bare `except` that swallows silently: "
          "the handler must re-raise, log, or use the caught exception — "
          "or the except type must be narrowed.",
      fixture_fail=_BROAD_FIXTURE_FAIL, fixture_pass=_BROAD_FIXTURE_PASS)
def broad_except(project: Project):
    for mod in project.modules:
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.ExceptHandler) and _is_broad(node) and \
                    _handler_swallows(node):
                yield _finding(
                    mod, node,
                    "broad except swallows the exception without logging, "
                    "re-raising, or recording it — narrow the type or add "
                    "context (trace/job id) to a log line")


# ============================================================ 11. dtype-flow
# Implicit-promotion hazards in the NUMERICS-declaring (jitting) modules
# (ISSUE 15): a dtype-less jnp constructor mints a weak/x64-dependent
# dtype, a float64 value flowing into a jnp op silently promotes the
# declared-f32 graph (and flips ULP behavior the committed contracts
# pin), and a non-f32-exact bare float literal inside a jnp call changes
# value the moment someone flips jax_enable_x64.  Deliberate escapes are
# annotated `# smlint: dtype-ok[reason]`.
_JNP_CONSTRUCTORS = {
    # name -> positional index where dtype may legally appear (None =
    # keyword-only, because the positional form is ambiguous)
    "zeros": 1, "ones": 1, "empty": 1, "full": 2, "asarray": 1, "array": 1,
    "arange": None, "linspace": None, "eye": None,
}
_DTYPE_CAST_NAMES = ("float32", "float16", "bfloat16", "int8", "int16",
                     "int32", "int64", "uint8", "uint32", "bool_",
                     "float64", "double")
_F64_NAMES = ("float64", "double")


def _jnp_chain(chain: str) -> bool:
    """Is ``chain`` a jax-numpy/lax callable path (jnp.*, lax.*, jax.*)?"""
    root = chain.split(".")[0]
    return root in ("jnp", "lax") or chain.startswith("jax.")


def _numerics_decl(mod) -> tuple[dict[str, tuple[str, int]] | None, int]:
    """The module's ``NUMERICS = numerics_surface(_, {...})`` declaration:
    ({site: (policy, lineno)}, decl lineno), or (None, 0) — the exact
    mirror of ``_surface_decl``."""
    for node in mod.tree.body:
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1 and
                isinstance(node.targets[0], ast.Name) and
                node.targets[0].id == "NUMERICS"):
            continue
        if not (isinstance(node.value, ast.Call) and
                _call_name(node.value) == "numerics_surface" and
                len(node.value.args) >= 2 and
                isinstance(node.value.args[1], ast.Dict)):
            return {}, node.lineno    # declared but not the literal grammar
        out = {}
        for k, v in zip(node.value.args[1].keys, node.value.args[1].values):
            ks, vs = _const_str(k), _const_str(v)
            if ks is not None:
                out[ks] = (vs or "", getattr(k, "lineno", node.lineno))
        return out, node.lineno
    return None, 0


def _f32_exact(v: float) -> bool:
    """Is ``v`` exactly representable in float32 (so its value is
    identical at every promotion width)?"""
    try:
        return struct.unpack("f", struct.pack("f", v))[0] == v
    except (OverflowError, struct.error):
        return False


def _is_f64_dtype_expr(e: ast.AST) -> bool:
    """``np.float64`` / ``jnp.float64`` / ``"float64"`` / bare ``float``
    used as a dtype value."""
    chain = _attr_chain(e)
    if chain.split(".")[-1] in _F64_NAMES:
        return True
    if isinstance(e, ast.Name) and e.id == "float":
        return True
    return _const_str(e) in ("float64", "double")


def _f64_source(node: ast.AST) -> bool:
    """Taint source for the f64-flow walk: a ``np.float64``/``np.double``
    scalar mint, an ``.astype(float64-ish)`` cast, or any call carrying a
    ``dtype=float64-ish`` keyword."""
    if not isinstance(node, ast.Call):
        return False
    callee = _call_name(node)
    if callee in _F64_NAMES and \
            _attr_chain(node.func).split(".")[0] in ("np", "numpy", "jnp"):
        return True
    if callee == "astype" and node.args and _is_f64_dtype_expr(node.args[0]):
        return True
    return any(kw.arg == "dtype" and _is_f64_dtype_expr(kw.value)
               for kw in node.keywords)


_DF_FIXTURE_FAIL = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from ..analysis.numerics import numerics_surface\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'score': 'contract=ulp(4); test=tests/test_x.py::test_score',\n"
        "})\n"
        "def score(x):\n"
        "    idx = jnp.arange(x.shape[0])\n"
        "    w = np.float64(0.5)\n"
        "    y = jnp.where(x > 0, x * 1e-30, 0.0)\n"
        "    return jnp.sum(y * w) + idx\n"
    ),
}
_DF_FIXTURE_PASS = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "from ..analysis.numerics import numerics_surface\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'score': 'contract=ulp(4); test=tests/test_x.py::test_score',\n"
        "})\n"
        "def score(x):\n"
        "    idx = jnp.arange(x.shape[0], dtype=jnp.int32)\n"
        "    w = np.float32(0.5)\n"
        "    y = jnp.where(x > 0, x * np.float32(1e-30), 0.0)\n"
        "    # smlint: dtype-ok[f64 epilogue runs on host after the fetch]\n"
        "    z = jnp.asarray(np.float64(2.0), dtype=jnp.float32)\n"
        "    return jnp.sum(y * w) * z + idx\n"
    ),
}


@rule("dtype-flow", severity="error",
      doc="In NUMERICS-declaring (jitting) modules: jnp constructors "
          "(zeros/ones/full/arange/asarray/...) must pass an explicit "
          "dtype (a dtype-less constructor mints a weak/x64-dependent "
          "type); float64 values (np.float64/np.double mints, "
          ".astype(float64), dtype=float64 kwargs) must not flow into "
          "jnp/lax calls — tracked through locals and single-level call "
          "summaries by the shared dataflow engine; and non-f32-exact "
          "bare float literals inside jnp/lax call arguments must be "
          "wrapped in an explicit dtype cast.  Deliberate escapes carry "
          "`# smlint: dtype-ok[reason]` (empty reason = finding).",
      fixture_fail=_DF_FIXTURE_FAIL, fixture_pass=_DF_FIXTURE_PASS)
def dtype_flow(project: Project):
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue
        decl, _ = _numerics_decl(mod)
        if decl is None:
            continue                  # not a declared-precision module

        def annotated(node) -> tuple[bool, bool]:
            """(skip, empty_reason) for the dtype-ok annotation."""
            reason = mod.annotation_reason("dtype", node.lineno)
            return reason is not None and reason != "", reason == ""

        # (a) dtype-less jnp constructors + (c) non-exact bare literals
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call):
                chain = _attr_chain(node.func)
                callee = _call_name(node)
                if _jnp_chain(chain) and callee in _JNP_CONSTRUCTORS:
                    pos = _JNP_CONSTRUCTORS[callee]
                    has_dtype = any(kw.arg == "dtype"
                                    for kw in node.keywords) or (
                        pos is not None and len(node.args) > pos)
                    if not has_dtype:
                        ok, empty = annotated(node)
                        if ok:
                            continue
                        yield _finding(
                            mod, node,
                            f"dtype-less jnp.{callee}() in a declared-"
                            f"precision module mints a weak/x64-dependent "
                            f"dtype — pass dtype= explicitly or annotate "
                            f"`# smlint: dtype-ok[reason]`"
                            + (" (annotation reason is empty)" if empty
                               else ""))
                continue
            if not (isinstance(node, ast.Constant) and
                    isinstance(node.value, float)):
                continue
            if _f32_exact(node.value):
                continue              # value identical at every width
            in_jnp, sanitized = False, False
            for anc in mod.ancestors(node):
                if isinstance(anc, ast.Call):
                    if _call_name(anc) in _DTYPE_CAST_NAMES:
                        sanitized = True   # np.float32(lit): explicit width
                        break
                    if _jnp_chain(_attr_chain(anc.func)):
                        in_jnp = True
                        break
                if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    break
            if in_jnp and not sanitized:
                ok, empty = annotated(node)
                if ok:
                    continue
                yield _finding(
                    mod, node,
                    f"bare float literal {node.value!r} is not exactly "
                    f"representable in float32 but rides a jnp/lax call — "
                    f"its weak-f64 value changes under jax_enable_x64; "
                    f"wrap it in np.float32(...) or annotate "
                    f"`# smlint: dtype-ok[reason]`"
                    + (" (annotation reason is empty)" if empty else ""))
        # (b) float64 values flowing into jnp/lax calls (dataflow taint,
        # single-level call summaries)
        summaries = dataflow.summaries.get(mod)
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            taint = TaintTracker(source=_f64_source, summaries=summaries)
            for node in taint.walk(mod, fn):
                if not isinstance(node, ast.Call) or \
                        not _jnp_chain(_attr_chain(node.func)):
                    continue
                parts = list(node.args) + [kw.value for kw in node.keywords]
                mints_f64 = any(kw.arg == "dtype" and
                                _is_f64_dtype_expr(kw.value)
                                for kw in node.keywords)
                if not (mints_f64 or
                        any(taint.expr_tainted(p) for p in parts)):
                    continue
                ok, empty = annotated(node)
                if ok:
                    continue
                yield _finding(
                    mod, node,
                    f"a float64 value flows into {_attr_chain(node.func) or _call_name(node)}() "
                    f"in a declared-f32 jitting module — the implicit "
                    f"promotion silently changes the graph's precision; "
                    f"cast to the declared dtype first or annotate "
                    f"`# smlint: dtype-ok[reason]`"
                    + (" (annotation reason is empty)" if empty else ""))


# ======================================================= 12. masked-reduction
# PR 13's shape-bucket lattice pads pixel rows and resident peaks; any
# reduction over an axis carrying that padding that skips the n_real
# masked helpers (batch_metrics(n_real=) / ops/moments_pallas.batch_
# moments family) produces wrong-but-plausible metrics.  Taint enters a
# function through parameters the NUMERICS entry declares `padded=` and
# through ops/buckets padding-helper calls; raw reductions over tainted
# values fire unless annotated `# smlint: masked-ok[reason]` (the
# argument why THIS reduction is pad-invariant).
_MASKED_HELPERS = ("batch_metrics", "batch_moments", "batch_moments_jnp",
                   "batch_moments_pallas_masked")
_REDUCTION_METHODS = ("sum", "mean", "max", "min", "prod", "std", "var",
                      "dot")
_REDUCTION_FUNCS = _REDUCTION_METHODS + (
    "einsum", "tensordot", "segment_sum", "vdot", "inner", "matmul",
    "average", "nansum", "nanmean", "amax", "amin")
_BUCKET_PAD_HELPERS = ("row_bucket", "peak_bucket", "pixel_bucket",
                       "pow2ish", "batch_bucket_down")


def _bucket_pad_source(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and \
        _call_name(node) in _BUCKET_PAD_HELPERS


def _masked_helper_clears(call: ast.Call) -> bool:
    """A masked-helper call consuming the padded block TOGETHER with its
    real-element count launders the taint: batch_metrics/batch_moments*
    with an n_real keyword, or the masked Pallas kernel's positional
    (images, n_real) form."""
    callee = _call_name(call)
    if callee not in _MASKED_HELPERS:
        return False
    if any(kw.arg == "n_real" for kw in call.keywords):
        return True
    return callee == "batch_moments_pallas_masked" and len(call.args) >= 2


_MR_FIXTURE_FAIL = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax.numpy as jnp\n"
        "from ..analysis.numerics import numerics_surface\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'score': 'contract=bit_exact; test=tests/test_x.py::test_s; "
        "padded=images',\n"
        "})\n"
        "def score(images, n_real):\n"
        "    mean = images.mean(axis=-1)\n"
        "    return mean\n"
    ),
}
_MR_FIXTURE_PASS = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "import jax.numpy as jnp\n"
        "from ..analysis.numerics import numerics_surface\n"
        "from ..ops.metrics_jax import batch_metrics\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'score': 'contract=bit_exact; test=tests/test_x.py::test_s; "
        "padded=images',\n"
        "})\n"
        "def score(images, theor, nv, n_real):\n"
        "    out = batch_metrics(images, theor, nv, 8, 8, n_real=n_real)\n"
        "    # smlint: masked-ok[zero pads are never positive; the count "
        "is exact]\n"
        "    npos = jnp.sum(images > 0, axis=-1)\n"
        "    return out, npos\n"
    ),
}


@rule("masked-reduction", severity="error",
      doc="In NUMERICS-declaring modules, reductions (sum/mean/max/dot/"
          "einsum/segment_sum/...) over values tainted by lattice "
          "padding — parameters the site's NUMERICS entry declares "
          "`padded=`, or locals derived from ops/buckets padding helpers "
          "(row_bucket/peak_bucket/pow2ish/...) — must flow through the "
          "n_real masked helpers (batch_metrics(n_real=), the "
          "batch_moments family) or carry a `# smlint: masked-ok[reason]` "
          "annotation arguing pad-invariance.  Taint is structural: a "
          "masked-helper call's RESULT is clean; everything else "
          "propagates.",
      fixture_fail=_MR_FIXTURE_FAIL, fixture_pass=_MR_FIXTURE_PASS)
def masked_reduction(project: Project):
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue
        decl, _ = _numerics_decl(mod)
        if not decl:
            continue
        padded: dict[str, set[str]] = {}
        for site, (policy, _ln) in decl.items():
            try:
                parsed = numerics_mod.parse_policy(policy)
            except ValueError:
                continue              # ulp-contract owns grammar findings
            if "padded" in parsed:
                padded[site] = {p.strip()
                                for p in parsed["padded"].split(",")}
        for fn in ast.walk(mod.tree):
            if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            taint = TaintTracker(source=_bucket_pad_source,
                                 call_clears=_masked_helper_clears,
                                 structural=True)
            params = {a.arg for a in (fn.args.posonlyargs + fn.args.args +
                                      fn.args.kwonlyargs)}
            taint.names |= padded.get(fn.name, set()) & params
            for node in taint.walk(mod, fn):
                if not isinstance(node, ast.Call):
                    continue
                callee = _call_name(node)
                chain = _attr_chain(node.func)
                root = chain.split(".")[0]
                what = None
                if callee in _REDUCTION_FUNCS and (
                        root in ("jnp", "np", "numpy", "lax") or
                        chain.startswith("jax.")):
                    # function form: jnp.sum(x) / np.mean(x) / lax....
                    parts = list(node.args) + \
                        [kw.value for kw in node.keywords]
                    if any(taint.expr_tainted_rec(p) for p in parts):
                        what = f"{chain}()"
                elif isinstance(node.func, ast.Attribute) and \
                        callee in _REDUCTION_METHODS:
                    # method form: x.sum() / x.mean() on a tainted receiver
                    if taint.expr_tainted_rec(node.func.value):
                        what = f".{callee}()"
                if what is None:
                    continue
                reason = mod.annotation_reason("masked", node.lineno)
                if reason:
                    continue
                if reason == "":
                    yield _finding(
                        mod, node,
                        f"masked-ok annotation for {what} has an empty "
                        f"reason — the pad-invariance argument is the "
                        f"point")
                else:
                    yield _finding(
                        mod, node,
                        f"{what} reduces over a lattice-padded axis "
                        f"without the n_real masked helpers "
                        f"(batch_metrics(n_real=)/batch_moments) — pad "
                        f"slots silently join the reduction; route "
                        f"through a masked helper or annotate "
                        f"`# smlint: masked-ok[why pad-invariant]`")


# ========================================================== 13. ulp-contract
_UC_FIXTURE_FAIL = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "from ..analysis.surface import compile_surface\n"
        "from ..analysis.numerics import numerics_surface\n"
        "COMPILE_SURFACE = compile_surface(__name__, {\n"
        "    'score': 'statics=none; buckets=single shape',\n"
        "    'other': 'statics=none; buckets=single shape',\n"
        "})\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'score': 'contract=ulp(4); test=tests/test_x.py::test_gone',\n"
        "    'ghost': 'contract=bit_exact; test=tests/test_x.py::test_a',\n"
        "})\n"
        "def score(x):\n"
        "    return x\n"
        "def other(x):\n"
        "    return x\n"
    ),
    "aux": {"tests/test_x.py": "def test_a():\n    pass\n"},
}
_UC_FIXTURE_PASS = {
    "sm_distributed_tpu/ops/x_jax.py": (
        "from ..analysis.surface import compile_surface\n"
        "from ..analysis.numerics import numerics_surface\n"
        "COMPILE_SURFACE = compile_surface(__name__, {\n"
        "    'score': 'statics=none; buckets=single shape',\n"
        "})\n"
        "NUMERICS = numerics_surface(__name__, {\n"
        "    'score': 'contract=bit_exact; test=tests/test_x.py::test_a',\n"
        "})\n"
        "def score(x):\n"
        "    return x\n"
    ),
    "aux": {"tests/test_x.py": "def test_a():\n    assert True\n"},
}


@rule("ulp-contract", severity="error",
      doc="Every COMPILE_SURFACE site must declare a numerics contract in "
          "the module's NUMERICS = numerics_surface(__name__, {...}) "
          "registry — `contract=bit_exact|ulp(N); test=<file>.py::<name>` "
          "— and every contract must be cross-referenced by a committed "
          "test that asserts it (the file must exist and define the "
          "test).  Dead NUMERICS entries (naming neither a surface site "
          "nor a function in the module), grammar violations, and "
          "padded= parameters that don't exist on the named function are "
          "findings too.",
      fixture_fail=_UC_FIXTURE_FAIL, fixture_pass=_UC_FIXTURE_PASS)
def ulp_contract(project: Project):
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue
        surface, surface_line = _surface_decl(mod)
        decl, decl_line = _numerics_decl(mod)
        if decl is None:
            if surface is not None:
                yield Finding(
                    "", "", mod.path, surface_line or 1,
                    f"module declares a COMPILE_SURFACE ({len(surface or {})} "
                    f"site(s)) but no NUMERICS = numerics_surface(__name__, "
                    f"{{...}}) registry — every compiled site needs a "
                    f"declared numerics contract (bit_exact or ulp(N)) "
                    f"before precision work can touch it",
                    anchor="NUMERICS")
            continue
        fns: dict[str, ast.AST] = {
            n.name: n for n in ast.walk(mod.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        # surface sites must carry contracts
        for site in sorted(surface or {}):
            if site not in decl:
                yield Finding(
                    "", "", mod.path, (surface or {})[site][1],
                    f"COMPILE_SURFACE site {site!r} has no NUMERICS "
                    f"contract — declare contract=bit_exact or ulp(N) "
                    f"with its proving test",
                    anchor=f"NUMERICS.{site}")
        for site, (policy, lineno) in sorted(decl.items()):
            try:
                parsed = numerics_mod.parse_policy(policy)
            except ValueError as exc:
                yield Finding(
                    "", "", mod.path, lineno,
                    f"NUMERICS entry {site!r}: {exc}",
                    anchor=f"NUMERICS.{site}")
                continue
            if site not in (surface or {}) and site not in fns:
                yield Finding(
                    "", "", mod.path, lineno,
                    f"NUMERICS entry {site!r} names neither a "
                    f"COMPILE_SURFACE site nor a function in this module "
                    f"(dead entry — remove it or fix the site name)",
                    anchor=f"NUMERICS.{site}")
                continue
            test_path, _, test_name = parsed["test"].partition("::")
            src = project.read(test_path)
            if src is None:
                tmod = project.module(test_path)
                src = tmod.source if tmod is not None else None
            if src is None:
                yield Finding(
                    "", "", mod.path, lineno,
                    f"NUMERICS entry {site!r}: contract test file "
                    f"{test_path!r} does not exist — a contract without "
                    f"its proving test is an unbacked promise",
                    anchor=f"NUMERICS.{site}.test")
            elif f"def {test_name}(" not in src:
                yield Finding(
                    "", "", mod.path, lineno,
                    f"NUMERICS entry {site!r}: {test_path!r} does not "
                    f"define {test_name!r} — the contract's "
                    f"cross-referenced test is gone",
                    anchor=f"NUMERICS.{site}.test")
            if "padded" in parsed:
                fn = fns.get(site)
                if fn is None:
                    yield Finding(
                        "", "", mod.path, lineno,
                        f"NUMERICS entry {site!r} declares padded= but "
                        f"names no function in this module the parameters "
                        f"could belong to",
                        anchor=f"NUMERICS.{site}.padded")
                else:
                    params = {a.arg for a in (
                        fn.args.posonlyargs + fn.args.args +
                        fn.args.kwonlyargs)}
                    for p in parsed["padded"].split(","):
                        if p.strip() not in params:
                            yield Finding(
                                "", "", mod.path, lineno,
                                f"NUMERICS entry {site!r}: padded "
                                f"parameter {p.strip()!r} is not a "
                                f"parameter of {site}()",
                                anchor=f"NUMERICS.{site}.padded")


def numerics_census(project: Project) -> dict[str, int]:
    """Static totals for the analysis drift sentinel: declared numerics
    contracts and the modules carrying a registry (scripts/smlint.py
    emits them as sm_numerics_* fields; rising counts diff across the
    ANALYSIS_r*.json history like any other surface growth)."""
    contracts = modules = 0
    for mod in project.modules:
        if not mod.path.startswith("sm_distributed_tpu/"):
            continue
        decl, _ = _numerics_decl(mod)
        if decl:
            modules += 1
            contracts += len(decl)
    return {"contracts": contracts, "modules": modules}
